"""Device-plane observability: batching/queue telemetry + flight recorder.

The serving-plane metrics (metrics.py) mirror the reference's
prometheus_metrics.rs surface; this module makes the TPU plane —
micro-batcher queues, device batch phases, shard table occupancy —
legible without attaching a debugger: a gap between the kernel's rate
and the served path needs something in /metrics to localize it.

Three pieces:

* :class:`DeviceStatsRecorder` — the sink the batchers/pipelines write
  flush-level telemetry into (queue waits, fill ratios, flush reasons,
  per-phase timings). A batcher holds ``recorder = None`` until
  ``set_metrics`` wires one up, and every per-decision instrumentation
  site is guarded by that single ``is not None`` check — the same
  no-op-when-detached discipline as ``tracing.py``'s ``_enabled`` gate.
* :class:`FlightRecorder` — a bounded buffer of the slowest-N recent
  decisions (request id, namespace, batch id, per-phase timings),
  served on ``GET /debug/stats``.
* :class:`JaxProfiler` — on-demand ``jax.profiler`` trace capture
  behind ``POST /debug/profile``.

Per-batch phase names (``PHASES``):

* ``dispatch`` — flush decision to the dispatch thread picking the
  batch up (executor queueing + loop scheduling),
* ``host_cache`` — decision-plan cache lookup + cached-lane staging
  (native pipeline; zero on pipelines without the cache),
* ``native_lane`` — the zero-Python hot lane's one C call: plan-mirror
  lookup, columnar staging into the pre-allocated upload buffers and
  begin-time response codes (native pipeline; zero with the lane off),
* ``host_stage`` — hit-array construction + kernel launch on the
  dispatch thread for the rows the cache missed,
* ``device_sync`` — device round trip: blocking on the launched kernel
  and the device->host transfer,
* ``unpack`` — decoding results and resolving futures,
* ``lease`` — one lease-broker refresh pass (settle stranded tokens +
  batched grant debits; lease/broker.py — zero with the tier off).
"""

from __future__ import annotations

import heapq
import itertools
import threading
import time
from contextvars import ContextVar
from typing import Dict, Iterable, List, Optional

__all__ = [
    "PHASES",
    "FLUSH_REASONS",
    "BATCHERS",
    "FlightRecorder",
    "DeviceStatsRecorder",
    "JaxProfiler",
    "ProfilerStateError",
    "current_request_id",
    "set_request_id",
    "collect_debug_stats",
]

PHASES = ("dispatch", "host_cache", "native_lane", "host_stage",
          "device_sync", "unpack", "lease")
FLUSH_REASONS = ("size", "deadline", "shutdown")
# The two queues feeding the batcher_* families: the decision path's
# MicroBatcher vs the write path's UpdateBatcher. Labeled apart because
# their steady states differ — the update batcher lingers to its
# deadline by design, and unlabeled it would drown the check path's
# fill-ratio/flush-reason signal.
BATCHERS = ("check", "update")

# Request-id propagation from the serving plane (server/middleware.py sets
# it per HTTP request / gRPC call) down to the batcher, so flight-recorder
# entries correlate with access logs without threading an argument through
# every storage layer.
_request_id: ContextVar[Optional[str]] = ContextVar(
    "limitador_tpu_request_id", default=None
)


def current_request_id() -> Optional[str]:
    return _request_id.get()


def set_request_id(request_id: Optional[str]) -> None:
    _request_id.set(request_id)


class FlightRecorder:
    """Bounded record of the slowest recent decisions.

    A size-``capacity`` min-heap keyed by total decision duration: a new
    decision enters only by beating the current fastest resident, which
    is also the eviction order — the buffer converges on the slowest-N
    seen since the last ``clear``. Thread-safe (decisions resolve on
    collect threads)."""

    def __init__(self, capacity: int = 32):
        self.capacity = max(int(capacity), 1)
        self._heap: List[tuple] = []  # (duration_s, seq, entry)
        self._seq = itertools.count()
        self._lock = threading.Lock()

    def would_admit(self, duration_s: float) -> bool:
        """Lock-free pre-check so callers skip building entry dicts for
        decisions that cannot enter (racy by design; ``offer`` re-checks
        under the lock)."""
        heap = self._heap
        return len(heap) < self.capacity or duration_s > heap[0][0]

    def offer(self, duration_s: float, entry: dict) -> None:
        with self._lock:
            if len(self._heap) < self.capacity:
                heapq.heappush(
                    self._heap, (duration_s, next(self._seq), entry)
                )
            elif duration_s > self._heap[0][0]:
                heapq.heapreplace(
                    self._heap, (duration_s, next(self._seq), entry)
                )

    def snapshot(self) -> List[dict]:
        """Entries slowest-first, each with a ``duration_ms`` field."""
        with self._lock:
            items = sorted(self._heap, key=lambda t: (-t[0], t[1]))
        return [
            dict(entry, duration_ms=round(duration * 1e3, 3))
            for duration, _seq, entry in items
        ]

    def clear(self) -> None:
        with self._lock:
            self._heap.clear()


class DeviceStatsRecorder:
    """Flush-level telemetry sink shared by the batchers and pipelines.

    Holds the process's flight recorder and flush-reason tallies, and —
    when constructed with a :class:`PrometheusMetrics` — observes queue
    waits, fill ratios, flush reasons and phase timings straight into
    the new metric families. Constructed by ``set_metrics``; detached
    batchers never touch one."""

    def __init__(self, metrics=None, flight_capacity: int = 32):
        # Duck-typed metrics sinks (bench.py's latency collector, test
        # fakes) may carry only a subset of the families; a recorder
        # raising mid-flush would strand every future of that batch, so
        # partial sinks degrade to flight-recorder-only instead.
        if metrics is not None and not all(
            hasattr(metrics, attr)
            for attr in ("batcher_flushes", "batcher_batch_fill_ratio",
                         "batcher_queue_wait", "device_phase_latency")
        ):
            metrics = None
        self.metrics = metrics
        self.flight = FlightRecorder(flight_capacity)
        # Process flight recorder (observability/flight.py, ISSUE 16):
        # the always-on sampled-exemplar tap riding the per-decision
        # loop below. None = detached, zero cost; the tap itself is
        # lock-free on the unsampled path (FLIGHT_TAP_BUDGET_NS).
        self.flight_tap = None
        self.flush_reasons: Dict[str, int] = dict.fromkeys(FLUSH_REASONS, 0)
        self._lock = threading.Lock()
        self._batch_ids = itertools.count(1)
        # Admission-plane congestion feed: called with the check
        # batcher's per-flush queue-wait list (admission/overload.py
        # AIMD signal). None = detached, zero cost.
        self.on_queue_waits = None
        # SLO watchdog (observability/native_plane.SloWatchdog): fed the
        # per-decision end-to-end latencies record_batch already has in
        # hand, one lock per batch. None = detached, zero cost.
        self.slo = None
        # Control-signal taps (observability/signals.SignalBus): EWMAs
        # of the check path's per-flush worst queue wait and fill
        # ratio, updated in record_flush — two float ops per flush, so
        # the bus never has to read histograms back out of Prometheus.
        self.signal_queue_wait_s = 0.0
        self.signal_batch_fill = 0.0
        # Serving-model observatory (observability/model.py): per-launch
        # observations (rows, host/device split, queue wait) feed the
        # online coefficient fit. The tap is a lock + bounded append on
        # the estimator side (perf-smoke MODEL_INGEST_BUDGET_US); the
        # fit itself runs on the observatory drain thread. None =
        # detached, zero cost.
        self.model = None
        try:
            from .model import model_fit_enabled, process_estimator

            if model_fit_enabled():
                self.model = process_estimator()
        except Exception:
            pass  # the recorder must construct without the fit

    def next_batch_id(self) -> int:
        return next(self._batch_ids)

    def record_flush(
        self,
        reason: str,
        fill_ratio: float,
        queue_waits: Iterable[float],
        batcher: str = "check",
    ) -> None:
        queue_waits = list(queue_waits)
        with self._lock:
            self.flush_reasons[reason] = self.flush_reasons.get(reason, 0) + 1
        if batcher == "check":
            # Signal taps (racy float EWMAs by design: a torn read
            # costs one sample of smoothing, never correctness).
            self.signal_queue_wait_s += 0.2 * (
                max(queue_waits, default=0.0) - self.signal_queue_wait_s
            )
            self.signal_batch_fill += 0.2 * (
                min(fill_ratio, 1.0) - self.signal_batch_fill
            )
        if batcher == "check" and self.on_queue_waits is not None:
            try:
                self.on_queue_waits(queue_waits)
            except Exception:
                pass  # congestion feedback must never fail a flush
        m = self.metrics
        if m is None:
            return
        m.batcher_flushes.labels(batcher, reason).inc()
        m.batcher_batch_fill_ratio.labels(batcher).observe(min(fill_ratio, 1.0))
        observe = m.batcher_queue_wait.labels(batcher).observe
        for wait in queue_waits:
            observe(wait)

    def record_chunks(self, chunk_hits: List[int]) -> None:
        """One flush's chunked-dispatch plan: how many sub-batches it
        split into and each chunk's hit count (dispatch_chunk_* families;
        getattr-guarded — duck-typed sinks may carry a subset)."""
        m = self.metrics
        if m is None:
            return
        splits = getattr(m, "dispatch_chunk_splits", None)
        if splits is not None:
            splits.observe(len(chunk_hits))
        hist = getattr(m, "dispatch_chunk_hits", None)
        if hist is not None:
            for hits in chunk_hits:
                hist.observe(hits)

    def record_phases(self, phases: Dict[str, float]) -> None:
        m = self.metrics
        if m is None:
            return
        for phase, seconds in phases.items():
            m.device_phase_latency.labels(phase).observe(seconds)

    def record_decision(
        self,
        duration_s: float,
        request_id: Optional[str],
        namespace: Optional[str],
        batch_id: int,
        queue_wait_s: float,
        phases_ms: Optional[dict] = None,
    ) -> None:
        """Offer one decided request to the flight recorder. Callers
        should gate on ``flight.would_admit`` to skip the argument
        marshalling for the fast majority (``record_batch`` does)."""
        self.flight.offer(duration_s, {
            "request_id": request_id,
            "namespace": None if namespace is None else str(namespace),
            "batch_id": batch_id,
            "queue_wait_ms": round(queue_wait_s * 1e3, 3),
            "phases_ms": phases_ms or {},
        })

    def record_batch(
        self,
        entries: Iterable[tuple],
        batch_id: int,
        t_flush: float,
        phases: Dict[str, float],
    ) -> None:
        """Flush-level fan-out for one finished batch, shared by all
        three pipelines: phase histograms plus flight-recorder offers for
        the decisions slow enough to matter. ``entries`` yields
        ``(t_enqueue, request_id, namespace)`` per decided request —
        namespace may be any object, stringified only on admission."""
        self.record_phases(phases)
        phases_ms = self.phases_ms(phases)
        flight = self.flight
        tap = self.flight_tap
        slo = self.slo
        totals: Optional[list] = [] if slo is not None else None
        t_now = time.perf_counter()
        n_rows = 0
        min_enq: Optional[float] = None
        for t_enq, rid, namespace in entries:
            n_rows += 1
            if min_enq is None or t_enq < min_enq:
                min_enq = t_enq
            total = t_now - t_enq
            if totals is not None:
                totals.append(total)
            if tap is not None:
                tap.tap(
                    total, "lean", request_id=rid,
                    namespace=namespace, phases_ms=phases_ms,
                )
            if flight.would_admit(total):
                self.record_decision(
                    total, rid, namespace, batch_id,
                    max(t_flush - t_enq, 0.0), phases_ms,
                )
        if totals:
            try:
                slo.observe_many(totals)
            except Exception:
                pass  # the watchdog must never fail a collect
        model = self.model
        if model is not None and n_rows:
            device_s = float(phases.get("device_sync", 0.0))
            # host target = the launch-shaped host WORK phases only.
            # native_lane is excluded deliberately: on the submit lane
            # its measured value absorbs event-loop interleaving (~10
            # µs/row of future machinery vs the C call's real ~0.3
            # µs/row — measured OLS R² 0.01 against rows), which would
            # drown the fit; lease is a broker refresh, not per-flush
            # work; dispatch is executor QUEUEING (it balloons under
            # sustained pressure, preferentially on small deadline
            # flushes — a negative-slope confounder), so it joins the
            # queue-wait side of the observation instead.
            host_s = sum(
                float(phases.get(k, 0.0))
                for k in ("host_cache", "host_stage", "unpack")
            )
            try:
                model.ingest(
                    n_rows, host_s, device_s,
                    max(t_flush - min_enq, 0.0)
                    + float(phases.get("dispatch", 0.0)),
                )
            except Exception:
                pass  # the fit must never fail a collect

    @staticmethod
    def phases_ms(phases: Dict[str, float]) -> dict:
        return {k: round(v * 1e3, 3) for k, v in phases.items()}


class ProfilerStateError(RuntimeError):
    """start while a capture is active / stop while idle."""


class JaxProfiler:
    """On-demand ``jax.profiler`` trace capture (one active trace per
    process — the jax profiler is a process-global singleton)."""

    def __init__(self, default_dir: str = "/tmp/limitador-tpu-profile"):
        self.default_dir = default_dir
        self._lock = threading.Lock()
        self._active_dir: Optional[str] = None
        self._started_at: Optional[float] = None

    def start(self, trace_dir: Optional[str] = None) -> str:
        import jax

        with self._lock:
            if self._active_dir is not None:
                raise ProfilerStateError(
                    f"profiler already capturing to {self._active_dir}"
                )
            target = trace_dir or self.default_dir
            jax.profiler.start_trace(target)
            self._active_dir = target
            self._started_at = time.time()
            return target

    def stop(self) -> str:
        import jax

        with self._lock:
            if self._active_dir is None:
                raise ProfilerStateError("no profiler capture active")
            # Clear BEFORE stop_trace: a failed flush (trace dir deleted
            # mid-capture, say) must not wedge the endpoint in
            # "already capturing" with no recovery short of a restart.
            target, self._active_dir = self._active_dir, None
            jax.profiler.stop_trace()
            return target

    def status(self) -> dict:
        with self._lock:
            active = self._active_dir is not None
            return {
                "active": active,
                "trace_dir": self._active_dir,
                "started_at": self._started_at if active else None,
            }


# -- /debug/stats ------------------------------------------------------------

_QUEUE_NAMES = {
    "MicroBatcher": "check_batcher",
    "UpdateBatcher": "update_batcher",
    "CompiledTpuLimiter": "compiled_pipeline",
    "NativeRlsPipeline": "native_pipeline",
}

#: attributes worth descending into when walking a limiter for
#: device-plane state (facade -> storage -> batchers -> device table;
#: "admission" reaches the admission controller hung off the storage).
_CHILD_ATTRS = (
    "storage", "counters", "batcher", "update_batcher", "inner", "_tpu",
    "limiter", "admission",
)


def collect_debug_stats(*sources) -> dict:
    """Walk limiters/storages/pipelines for device-plane state and shape
    the ``GET /debug/stats`` payload: per-queue depths, per-shard table
    occupancy, flush-reason tallies and the slow-decision flight
    recorder. Everything is getattr-driven so any storage topology
    degrades to what it actually has (an in-memory limiter reports empty
    lists, not an error)."""
    seen: set = set()
    queues: List[dict] = []
    shards: Dict[str, dict] = {}
    recorders: Dict[int, DeviceStatsRecorder] = {}
    admission: Dict[int, dict] = {}
    plan_caches: Dict[int, dict] = {}
    for source in sources:
        _walk(source, seen, queues, shards, recorders, admission,
              plan_caches)
    flush_reasons: Dict[str, int] = {}
    flights: List[dict] = []
    for recorder in recorders.values():
        for reason, count in recorder.flush_reasons.items():
            flush_reasons[reason] = flush_reasons.get(reason, 0) + count
        flights.extend(recorder.flight.snapshot())
    flights.sort(key=lambda e: -e.get("duration_ms", 0.0))
    out = {
        "queues": queues,
        "shards": list(shards.values()),
        "flush_reasons": flush_reasons,
        "flight_recorder": flights,
    }
    if admission:
        # One controller per process in practice; surface the first.
        out["admission"] = next(iter(admission.values()))
    if plan_caches:
        # Per-pipeline hot-descriptor decision-plan cache state (native
        # blob cache and/or compiled counter cache), keyed by type name.
        out["plan_cache"] = {
            name: stats for stats in plan_caches.values()
            for name in (stats.pop("_source"),)
        }
    return out


def _walk(source, seen, queues, shards, recorders, admission=None,
          plan_caches=None) -> None:
    if source is None or id(source) in seen:
        return
    seen.add(id(source))
    debug = getattr(source, "admission_debug", None)
    if callable(debug) and admission is not None:
        try:
            admission[id(source)] = debug()
        except Exception:
            pass
    cache_stats = getattr(source, "plan_cache_stats", None)
    if callable(cache_stats) and plan_caches is not None:
        try:
            stats = cache_stats()
        except Exception:
            stats = None
        if stats:
            stats = dict(stats)
            stats["_source"] = type(source).__name__
            plan_caches[id(source)] = stats
    for attr in ("recorder", "_recorder"):
        recorder = getattr(source, attr, None)
        if isinstance(recorder, DeviceStatsRecorder):
            recorders[id(recorder)] = recorder
    pending = getattr(source, "_pending", None)
    if hasattr(pending, "__len__"):
        name = type(source).__name__
        entry = {
            "queue": _QUEUE_NAMES.get(name, name),
            "depth": len(pending),
        }
        pending_hits = getattr(source, "_pending_hits", None)
        if pending_hits is not None:
            entry["pending_hits"] = int(pending_hits)
        queues.append(entry)
    device_stats = getattr(source, "device_stats", None)
    if callable(device_stats):
        try:
            # Keyed by shard label: a facade delegating to its inner
            # storage must not report the same table twice.
            for shard in device_stats().get("shards", ()):
                shards[str(shard.get("shard"))] = shard
        except Exception:
            pass
    for attr in _CHILD_ATTRS:
        child = getattr(source, attr, None)
        if child is not None and not isinstance(
            child, (int, float, str, bytes, bool, dict, list, tuple, set)
        ):
            _walk(child, seen, queues, shards, recorders, admission,
                  plan_caches)
