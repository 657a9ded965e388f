"""Native telemetry plane + SLO burn-rate watchdog (ISSUE 7).

PR 5/6 made the dominant traffic invisible: a repeat or leased
descriptor runs zero Python bytecode between socket and response, so
the flight recorder and per-phase histograms never saw the rows that
matter most. The C libraries now measure their own phases (wait-free
log2-ns histograms + a slow-row exemplar ring — ``hp_tel_*`` in
native/hostpath.cc, ``h2i_tel_*`` in native/h2ingress.cc); this module
is the Python half:

* :data:`PHASES` — the merged native phase set. ``hot_lookup`` /
  ``hot_stage`` / ``lease_hit`` / ``hot_finish`` come from the hostpath
  drain, ``h2i_respond`` from the ingress drain. tools/lint.py
  cross-checks that every entry here has a matching
  ``native_phase_<entry>`` histogram family declared in metrics.py.
* :class:`NativePlane` — drains the cumulative C histograms on every
  metrics render and feeds the per-bucket increments into the
  ``native_phase_*`` Prometheus families (recycle-proof accumulation:
  the C plane is process-global, and the Python side keeps per-bucket
  baselines exactly like the ``library_stats`` counters), drains slow-
  row exemplars into the process flight recorder under the
  ``native_lane``/``lease`` phases, and exports the SLO watchdog state
  as ``slo_*`` gauges plus ``/debug/stats`` sections.
* :class:`SloWatchdog` — multi-window (5m/1h) burn-rate tracking of the
  p99 <= 2 ms north-star budget over the merged host+device decision
  latency (fed per batch from ``DeviceStatsRecorder.record_batch``, the
  point where every batched decision's end-to-end duration is already
  in hand). Burn rate is the classic SRE form: the share of decisions
  over budget divided by the error budget (1 - target quantile); the
  watchdog fires only when BOTH windows burn, so a single slow batch
  can't page and a sustained regression can't hide.
* :func:`device_backed_runtime` — is a non-CPU jax backend actually
  serving this process? Exported as the ``device_backed`` gauge, a
  ``/debug/stats`` field and the bench rows' ``device_backed`` bit, so
  a deployment serving from the CPU on purpose is machine-visible.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from ..device import device_report

__all__ = [
    "PHASES",
    "METRIC_FAMILIES",
    "NATIVE_PHASE_BUCKETS",
    "NativePlane",
    "SloWatchdog",
    "device_backed_runtime",
]

#: every native phase the plane measures; tools/lint.py enforces a
#: ``native_phase_<entry>`` histogram family per entry
PHASES = ("hot_lookup", "hot_stage", "lease_hit", "hot_finish",
          "h2i_respond")

#: Prometheus families owned by this module (lint-enforced against the
#: declarations in observability/metrics.py)
METRIC_FAMILIES = (
    "native_phase_hot_lookup",
    "native_phase_hot_stage",
    "native_phase_lease_hit",
    "native_phase_hot_finish",
    "native_phase_h2i_respond",
    "slo_p99_ms_5m",
    "slo_p99_ms_1h",
    "slo_burn_rate_5m",
    "slo_burn_rate_1h",
    "slo_budget_ms",
    "slo_breached",
    "slo_breached_actionable",
    "device_backed",
)

# The C histograms are log2-ns: bucket b holds [2^b, 2^{b+1}) ns. The
# Prometheus families use a trimmed slice of the same pow2 edges (in
# seconds), so every C bucket maps into exactly ONE Prometheus bucket
# and merging a drain is per-bucket integer adds — no resampling, no
# per-observation Python.
_BUCKET_LO = 7   # C buckets below 2^8 ns collapse into the first edge
_BUCKET_HI = 33  # C buckets above 2^34 ns (~17 s) go to +Inf
#: Prometheus bucket edges (seconds): 2^{b+1} ns for b in [LO, HI]
NATIVE_PHASE_BUCKETS = tuple(
    2.0 ** (b + 1) / 1e9 for b in range(_BUCKET_LO, _BUCKET_HI + 1)
)


def _prom_bucket_index(c_bucket: int) -> int:
    """C log2 bucket -> index into a native_phase histogram's
    ``_buckets`` list (the +Inf slot is the last index)."""
    if c_bucket < _BUCKET_LO:
        return 0
    if c_bucket > _BUCKET_HI:
        return _BUCKET_HI - _BUCKET_LO + 1  # +Inf
    return c_bucket - _BUCKET_LO


_DEVICE_BACKED: Optional[bool] = None


def device_backed_runtime() -> Optional[bool]:
    """Is a non-CPU jax backend actually serving this process? None
    when jax was never imported (memory/disk servers must not pay a jax
    import for a diagnostics bit); cached after the first real answer.
    The process IS the deployment — only it can hold the chip — so
    asking its own backend is both cheap and the truth that matters."""
    global _DEVICE_BACKED
    if _DEVICE_BACKED is None:
        report = device_report()
        if report is None:
            return None
        _DEVICE_BACKED = report["platform"] != "cpu"
    return _DEVICE_BACKED


class SloWatchdog:
    """Multi-window burn-rate watchdog for the p99 <= budget SLO.

    Decision latencies land in a ring of 10 s slices, each a log2-µs
    histogram plus over-budget/total counters; the 5 m and 1 h windows
    are merges over the live slices. ``burn_rate`` is
    (share over budget) / (1 - quantile): 1.0 means the error budget is
    being consumed exactly as fast as the SLO allows, >1 means a real
    p99 breach over that window. ``breached`` requires BOTH windows to
    burn — the standard multi-window guard against paging on one slow
    batch (short window) or never un-paging after recovery (long
    window).

    Thread-safe; ``observe_many`` takes the lock once per batch. The
    ``clock`` injection exists for the burn-injection tests."""

    SLICE_S = 10.0
    _N_BUCKETS = 40  # log2 µs

    def __init__(
        self,
        budget_ms: float = 2.0,
        quantile: float = 0.99,
        short_s: float = 300.0,
        long_s: float = 3600.0,
        clock: Callable[[], float] = time.monotonic,
    ):
        self.budget_ms = float(budget_ms)
        self.quantile = float(quantile)
        self.short_s = float(short_s)
        self.long_s = float(long_s)
        self._clock = clock
        self._n_slices = max(int(long_s / self.SLICE_S), 1)
        self._short_slices = max(int(short_s / self.SLICE_S), 1)
        self._counts = np.zeros(
            (self._n_slices, self._N_BUCKETS), np.int64
        )
        self._total = np.zeros(self._n_slices, np.int64)
        self._over = np.zeros(self._n_slices, np.int64)
        self._cur_abs = None  # absolute slice id the ring head holds
        self._lock = threading.Lock()

    def _sync(self, now: float) -> int:
        """Advance the ring to ``now``'s slice, zeroing skipped slices;
        returns the ring row of the current slice. Caller holds the
        lock."""
        cur = int(now // self.SLICE_S)
        if self._cur_abs is None:
            self._cur_abs = cur
        elif cur > self._cur_abs:
            step = min(cur - self._cur_abs, self._n_slices)
            for i in range(1, step + 1):
                row = (self._cur_abs + i) % self._n_slices
                self._counts[row] = 0
                self._total[row] = 0
                self._over[row] = 0
            self._cur_abs = cur
        return self._cur_abs % self._n_slices

    def observe_many(self, seconds: List[float]) -> None:
        if not seconds:
            return
        us = np.maximum(np.asarray(seconds, np.float64) * 1e6, 1.0)
        buckets = np.clip(
            np.log2(us).astype(np.int64), 0, self._N_BUCKETS - 1
        )
        over = int((us > self.budget_ms * 1e3).sum())
        with self._lock:
            row = self._sync(self._clock())
            np.add.at(self._counts[row], buckets, 1)
            self._total[row] += us.shape[0]
            self._over[row] += over

    def observe(self, seconds: float) -> None:
        self.observe_many([seconds])

    def _window_rows(self, n_slices: int) -> np.ndarray:
        """Ring rows of the most recent ``n_slices`` slices (current
        included). Caller holds the lock."""
        head = self._cur_abs % self._n_slices
        return (head - np.arange(n_slices)) % self._n_slices

    def _window_stats(self, n_slices: int):
        rows = self._window_rows(n_slices)
        total = int(self._total[rows].sum())
        over = int(self._over[rows].sum())
        if total == 0:
            return 0, 0, 0.0
        counts = self._counts[rows].sum(axis=0)
        rank = self.quantile * total
        cum = np.cumsum(counts)
        b = min(int(np.searchsorted(cum, rank)), self._N_BUCKETS - 1)
        p_ms = 2.0 ** (b + 1) / 1e3  # bucket upper edge, µs -> ms
        return total, over, p_ms

    def status(self) -> dict:
        with self._lock:
            self._sync(self._clock())
            short_t, short_o, short_p = self._window_stats(
                self._short_slices
            )
            long_t, long_o, long_p = self._window_stats(self._n_slices)
        err_budget = max(1.0 - self.quantile, 1e-9)
        burn_short = (short_o / short_t / err_budget) if short_t else 0.0
        burn_long = (long_o / long_t / err_budget) if long_t else 0.0
        return {
            "budget_ms": self.budget_ms,
            "quantile": self.quantile,
            "p99_ms_5m": round(short_p, 4),
            "p99_ms_1h": round(long_p, 4),
            "burn_rate_5m": round(burn_short, 4),
            "burn_rate_1h": round(burn_long, 4),
            "samples_5m": short_t,
            "samples_1h": long_t,
            "breached": bool(burn_short >= 1.0 and burn_long >= 1.0),
        }


class NativePlane:
    """The Python half of the native telemetry plane: drains the C
    histograms/exemplars, merges them into Prometheus, and owns the SLO
    watchdog + runtime device_backed probe.

    Attach with ``metrics.attach_native_plane(plane)`` (polled on every
    render) and append to the HTTP API's ``debug_sources`` (the
    ``native_telemetry`` / ``slo_status`` / ``device_backed`` callables
    become ``/debug/stats`` sections). ``attach_recorder`` wires the
    watchdog into the device-plane recorder's per-batch latency feed
    and gives exemplars a flight recorder to land in."""

    def __init__(
        self,
        budget_ms: float = 2.0,
        slow_row_us: float = 0.0,
        trace_sample: int = 0,
        recorder=None,
        watchdog: Optional[SloWatchdog] = None,
    ):
        self.watchdog = watchdog or SloWatchdog(budget_ms=budget_ms)
        self.recorder = recorder
        if recorder is not None:
            recorder.slo = self.watchdog
        self.slow_row_us = float(slow_row_us)
        self.trace_sample = int(trace_sample)
        # per-(phase, field) cumulative baselines for increment
        # conversion (the C plane is process-global and never resets)
        self._base_buckets: Dict[str, np.ndarray] = {}
        self._base_sum: Dict[str, int] = {}
        self._lock = threading.Lock()
        self.configure()

    # -- configuration -------------------------------------------------------

    def configure(self) -> bool:
        """Arm the C planes (both libraries; each gated on its own
        export set). Returns True when the hostpath plane armed."""
        from .. import native

        armed = native.tel_config(
            True, int(self.slow_row_us * 1000.0), self.trace_sample
        )
        try:
            from ..native.ingress import ingress_tel_config

            ingress_tel_config(True)
        except Exception:
            pass  # ingress library absent/unbuilt: hostpath still counts
        return armed

    def attach_recorder(self, recorder) -> None:
        self.recorder = recorder
        if recorder is not None:
            recorder.slo = self.watchdog

    # -- drains --------------------------------------------------------------

    def snapshots(self) -> Dict[str, dict]:
        """Cumulative per-phase snapshots across BOTH libraries, keyed
        by the merged PHASES names. EVERY phase is present — a library
        that is not loaded (peek-gated drains; e.g. no native ingress)
        contributes zero-count entries, so the /debug/stats schema and
        the Prometheus surface are identical across configurations."""
        from .. import native

        snap = dict(native.tel_drain())
        try:
            from ..native.ingress import ingress_tel_drain

            h2i = ingress_tel_drain()
        except Exception:
            h2i = None
        if h2i is not None:
            snap["h2i_respond"] = h2i
        zero = None
        for phase in PHASES:
            if phase not in snap:
                if zero is None:
                    zero = {
                        "count": 0, "sum_ns": 0,
                        "buckets": [0] * native.TEL_BUCKETS,
                    }
                snap[phase] = dict(zero)
        return snap

    def drain_exemplars(self) -> List[dict]:
        from .. import native

        return native.tel_exemplars()

    # -- the render-time poll ------------------------------------------------

    def poll(self, metrics) -> None:
        """Called by ``PrometheusMetrics`` on every render: merge the
        drained histogram deltas into the ``native_phase_*`` families,
        land slow-row exemplars in the flight recorder, and refresh the
        ``slo_*`` / ``device_backed`` gauges."""
        with self._lock:
            for phase, snap in self.snapshots().items():
                hist = getattr(metrics, f"native_phase_{phase}", None)
                if hist is None:
                    continue
                buckets = np.asarray(snap["buckets"], np.int64)
                base = self._base_buckets.get(phase)
                if base is None:
                    base = np.zeros_like(buckets)
                delta = buckets - base
                if int(delta.sum()) <= 0:
                    continue
                self._base_buckets[phase] = buckets
                sum_s = (
                    snap["sum_ns"] - self._base_sum.get(phase, 0)
                ) / 1e9
                self._base_sum[phase] = snap["sum_ns"]
                # Bulk per-bucket feed: observe() per drained row would
                # cost a Python call per observation; the bucket counts
                # ARE the histogram, so add them directly (the render
                # cumulates buckets and derives _count itself).
                for b in np.nonzero(delta)[0].tolist():
                    hist._buckets[_prom_bucket_index(b)].inc(
                        int(delta[b])
                    )
                hist._sum.inc(max(sum_s, 0.0))
        self._offer_exemplars()
        wd = self.watchdog.status()
        for gauge, key in (
            (metrics.slo_p99_ms_5m, "p99_ms_5m"),
            (metrics.slo_p99_ms_1h, "p99_ms_1h"),
            (metrics.slo_burn_rate_5m, "burn_rate_5m"),
            (metrics.slo_burn_rate_1h, "burn_rate_1h"),
            (metrics.slo_budget_ms, "budget_ms"),
        ):
            gauge.set(wd[key])
        metrics.slo_breached.set(1 if wd["breached"] else 0)
        backed = device_backed_runtime()
        if backed is not None:
            metrics.device_backed.set(1 if backed else 0)
        # The PAGEABLE breach signal (ISSUE 14 satellite): on a
        # CPU-fallback box slo_breached fires legitimately but
        # un-actionably — the p99 budget was derived for device-backed
        # serving, and no operator action fixes a missing device. The
        # Grafana alert panel gates on THIS gauge; slo_breached stays
        # the raw truth.
        actionable = getattr(metrics, "slo_breached_actionable", None)
        if actionable is not None:
            actionable.set(1 if (wd["breached"] and backed) else 0)

    def _offer_exemplars(self) -> None:
        rec = self.recorder
        if rec is None:
            # No flight recorder to land in (yet): leave the C ring
            # alone — it keeps the latest 64 slow rows until a consumer
            # attaches, instead of discarding them on every render.
            return
        exemplars = self.drain_exemplars()
        if not exemplars:
            return
        tap = getattr(rec, "flight_tap", None)
        for ex in exemplars:
            phases_ms = {
                "native_lane": round(
                    (ex["lookup_ns"] + ex["stage_ns"]) / 1e6, 4
                ),
            }
            if ex["leased_rows"] > 0:
                phases_ms["lease"] = round(ex["total_ns"] / 1e6, 4)
            if tap is not None:
                # ISSUE 16: the zero-Python lane's slow rows ride the
                # native_hot lane of the process flight recorder (the
                # C ring IS the sample — every drained row taps).
                tap.tap(
                    ex["total_ns"] / 1e9, "native_hot",
                    phases_ms=phases_ms,
                    key=format(
                        ex["blob_digest"] & 0xFFFFFFFFFFFFFFFF, "016x"
                    ),
                )
            rec.flight.offer(ex["total_ns"] / 1e9, {
                "request_id": None,
                "namespace": None,
                "batch_id": None,
                "queue_wait_ms": 0.0,
                "phases_ms": phases_ms,
                "native": {
                    "rows": ex["rows"],
                    "kernel_rows": ex["kernel_rows"],
                    "staged_hits": ex["staged_hits"],
                    "miss_rows": ex["miss_rows"],
                    "leased_rows": ex["leased_rows"],
                    "blob_digest": format(
                        ex["blob_digest"] & 0xFFFFFFFFFFFFFFFF, "016x"
                    ),
                    "blob_len": ex["blob_len"],
                    "plan_kind": ex["plan_kind"],
                    "lease_tokens": ex["lease_tokens"],
                },
            })

    # -- /debug/stats sections -----------------------------------------------

    def native_telemetry(self) -> dict:
        """JSON-friendly summary per phase: counts, mean and p50/p99 µs
        derived from the cumulative log2 buckets."""
        out: dict = {}
        for phase, snap in self.snapshots().items():
            count = snap["count"]
            entry = {"count": count}
            if count:
                entry["mean_us"] = round(snap["sum_ns"] / count / 1e3, 3)
                buckets = np.asarray(snap["buckets"], np.int64)
                cum = np.cumsum(buckets)
                for q, name in ((0.5, "p50_us"), (0.99, "p99_us")):
                    b = int(np.searchsorted(cum, q * count))
                    b = min(b, buckets.shape[0] - 1)
                    entry[name] = round(2.0 ** (b + 1) / 1e3, 3)
            out[phase] = entry
        return out

    def slo_status(self) -> dict:
        """Watchdog status plus the device_backed companion: breached
        AND device-backed is the actionable (pageable) combination —
        a CPU-fallback breach is real but not operator-fixable."""
        status = self.watchdog.status()
        backed = device_backed_runtime()
        status["device_backed"] = backed
        status["actionable"] = bool(status["breached"] and backed)
        return status

    def device_backed(self) -> Optional[bool]:
        return device_backed_runtime()
