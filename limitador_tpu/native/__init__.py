"""ctypes binding for the native host path (native/hostpath.cc).

Builds the shared library through the shared builder
(limitador_tpu/native/build.py: $CXX -> g++ -> clang++, content-stamped)
on first use; ``available()`` gates every consumer — all native users
keep an exact pure-Python fallback, so a missing toolchain only costs
speed.

Besides the interner / RLS parser / slot map (PR r2), this binding
exposes the **zero-Python hot lane** (ISSUE 5): a C-side mirror of the
decision-plan cache plus one begin call that covers plan lookup,
columnar staging into pre-allocated kernel upload buffers and begin-time
response codes, and one finish call that turns the device result
columns into response codes + aggregated metrics. ctypes releases the
GIL around every call, and the begin passes run on a small worker pool
inside the library — the parallel host staging happens with no Python
frames and no GIL.
"""

from __future__ import annotations

import ctypes
import struct
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .build import NativeLib, build_status

__all__ = [
    "available",
    "lane_available",
    "lease_available",
    "tel_available",
    "tel_config",
    "tel_drain",
    "tel_exemplars",
    "build_error",
    "build_status",
    "staged_trace_attrs",
    "HostPath",
    "NativeHotLane",
    "LANE_MISS",
    "LANE_KERNEL",
    "LANE_OK",
    "LANE_UNKNOWN",
    "LANE_OVER",
    "LANE_ERROR",
    "LANE_FOREIGN",
    "LANE_FOREIGN_BASE",
    "pod_available",
    "pod_hash",
    "TEL_PHASES",
    "TEL_BUCKETS",
]

#: hot-lane outcome codes (mirror native/hostpath.cc LaneKind)
LANE_MISS = 0
LANE_KERNEL = 1
LANE_OK = 2
LANE_UNKNOWN = 3
LANE_OVER = 4
LANE_ERROR = 5
#: plan kind of a foreign-owned blob in the C mirror (never a row code)
LANE_FOREIGN = 6
#: a begin answers a foreign-owned row as LANE_FOREIGN_BASE + owner —
#: codes >= this are bulk-forward verdicts, not local outcomes
LANE_FOREIGN_BASE = 8

_INT32_MAX = (1 << 31) - 1

#: hostpath-local telemetry phases, in the C TelPhase enum order (the
#: h2ingress library's ``h2i_respond`` phase rides its own drain —
#: observability/native_plane.py merges both under one PHASES tuple)
TEL_PHASES = ("hot_lookup", "hot_stage", "lease_hit", "hot_finish")
#: log2-ns histogram buckets per phase: bucket b holds [2^b, 2^{b+1}) ns
TEL_BUCKETS = 40
#: int64 fields per drained slow-row exemplar (hp_tel_exemplars)
TEL_EX_STRIDE = 12

_LIB = NativeLib("hostpath", ["native/hostpath.cc"], ["-pthread"])
_sigs_lock = threading.Lock()
_sigs_done = False


def _bind(lib) -> None:
    lib.hp_new.restype = ctypes.c_void_p
    lib.hp_free.argtypes = [ctypes.c_void_p]
    lib.hp_track_key.restype = ctypes.c_int32
    lib.hp_track_key.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int32]
    lib.hp_intern.restype = ctypes.c_int32
    lib.hp_intern.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int32]
    lib.hp_find.restype = ctypes.c_int32
    lib.hp_find.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int32]
    lib.hp_string.restype = ctypes.c_int32
    lib.hp_string.argtypes = [
        ctypes.c_void_p, ctypes.c_int32,
        ctypes.POINTER(ctypes.c_char_p),
    ]
    lib.hp_interned_count.restype = ctypes.c_int64
    lib.hp_interned_count.argtypes = [ctypes.c_void_p]
    lib.hp_parse_batch.restype = ctypes.c_int32
    lib.hp_parse_batch.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p,
        np.ctypeslib.ndpointer(np.int32), ctypes.c_int32,
        np.ctypeslib.ndpointer(np.int32),
        np.ctypeslib.ndpointer(np.int32),
        np.ctypeslib.ndpointer(np.int32),
        np.ctypeslib.ndpointer(np.int32),
        np.ctypeslib.ndpointer(np.int32),
    ]
    lib.hp_slots_lookup.argtypes = [
        ctypes.c_void_p, np.ctypeslib.ndpointer(np.int32),
        ctypes.c_int32, ctypes.c_int32,
        np.ctypeslib.ndpointer(np.int64),
    ]
    lib.hp_slots_insert.argtypes = [
        ctypes.c_void_p, np.ctypeslib.ndpointer(np.int32),
        ctypes.c_int32, ctypes.c_int64,
    ]
    lib.hp_slots_remove.argtypes = [
        ctypes.c_void_p, np.ctypeslib.ndpointer(np.int32), ctypes.c_int32,
    ]
    lib.hp_slots_count.restype = ctypes.c_int64
    lib.hp_slots_count.argtypes = [ctypes.c_void_p]
    # -- hot lane (array params are raw pointers: the callers pass both
    # numpy buffers and the ingress's ctypes take arrays) --------------
    lib.hp_set_threads.argtypes = [ctypes.c_int32]
    lib.hp_plan_epoch.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.hp_plan_put.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int32, ctypes.c_int64,
        ctypes.c_int32, ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
        ctypes.c_void_p, ctypes.c_int32,
    ]
    # -- pod ownership mirror (ISSUE 13): crc32 verdict + plan stamps --
    lib.hp_pod_hash.restype = ctypes.c_int64
    lib.hp_pod_hash.argtypes = [ctypes.c_char_p, ctypes.c_int32]
    lib.hp_pod_config.restype = ctypes.c_int32
    lib.hp_pod_config.argtypes = [
        ctypes.c_void_p, ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
    ]
    lib.hp_pod_owner.restype = ctypes.c_int32
    lib.hp_pod_owner.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int32,
    ]
    lib.hp_plan_stamp_owner.restype = ctypes.c_int32
    lib.hp_plan_stamp_owner.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int32, ctypes.c_int64,
        ctypes.c_char_p, ctypes.c_int32,
    ]
    lib.hp_plan_set_owner.restype = ctypes.c_int32
    lib.hp_plan_set_owner.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int32, ctypes.c_int64,
        ctypes.c_int32,
    ]
    lib.hp_plan_invalidate_slot.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.hp_plan_count.restype = ctypes.c_int64
    lib.hp_plan_count.argtypes = [ctypes.c_void_p]
    if hasattr(lib, "hp_plan_export"):  # pre-ISSUE-18 prebuilt binary
        lib.hp_plan_export.restype = ctypes.c_int64
        lib.hp_plan_export.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
        ]
    lib.hp_lane_stats.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
    # -- quota leasing (lease/broker.py drives these under the native
    # lock; consume itself rides hp_hot_begin) -------------------------
    lib.hp_lease_config.argtypes = [
        ctypes.c_void_p, ctypes.c_int32, ctypes.c_int32,
    ]
    lib.hp_lease_grant.restype = ctypes.c_int32
    lib.hp_lease_grant.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int32, ctypes.c_int64,
        ctypes.c_int64, ctypes.c_int64,
    ]
    lib.hp_lease_revoke.restype = ctypes.c_int64
    lib.hp_lease_revoke.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int32, ctypes.c_int64,
    ]
    lib.hp_lease_tokens.restype = ctypes.c_int64
    lib.hp_lease_tokens.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int32, ctypes.c_int64,
    ]
    lib.hp_lease_drain_returns.restype = ctypes.c_int32
    lib.hp_lease_drain_returns.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int32,
    ]
    lib.hp_lease_candidates.restype = ctypes.c_int32
    lib.hp_lease_candidates.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_int32,
    ]
    lib.hp_lease_stats.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
    # -- tenant usage observatory (drains per-plan leased-admission
    # counts; observability/usage.py merges them into the heavy-hitter
    # table) ------------------------------------------------------------
    lib.hp_usage_drain.restype = ctypes.c_int32
    lib.hp_usage_drain.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_int32,
    ]
    # -- native telemetry plane (process-global; observability/
    # native_plane.py drains it) ---------------------------------------
    lib.hp_tel_config.argtypes = [
        ctypes.c_int32, ctypes.c_int64, ctypes.c_int64,
    ]
    lib.hp_tel_drain.restype = ctypes.c_int32
    lib.hp_tel_drain.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.hp_tel_exemplars.restype = ctypes.c_int32
    lib.hp_tel_exemplars.argtypes = [ctypes.c_void_p, ctypes.c_int32]
    lib.hp_hot_begin.restype = ctypes.c_int32
    lib.hp_hot_begin.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int32,
        ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_int64, ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
    ]
    lib.hp_hot_begin_buf.restype = ctypes.c_int32
    lib.hp_hot_begin_buf.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_void_p, ctypes.c_int32,
        ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_int64, ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
    ]
    lib.hp_hot_finish.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int32,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_void_p,
    ]
    lib.hp_partition_positions.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_int32,
        ctypes.c_void_p, ctypes.c_void_p,
    ]


def _load():
    global _sigs_done
    lib = _LIB.load()
    if lib is not None and not _sigs_done:
        with _sigs_lock:
            if not _sigs_done:
                _bind(lib)
                _sigs_done = True
                # Re-arm the telemetry state requested before the
                # library was built (tel_config only peeks).
                if _tel_desired is not None and hasattr(
                    lib, "hp_tel_config"
                ):
                    lib.hp_tel_config(*_tel_desired)
    return lib


def available() -> bool:
    return _load() is not None


def lane_available() -> bool:
    """True when the loaded library exports the hot-lane symbols (an old
    pre-stamped binary without them degrades to the pure-Python lane)."""
    lib = _load()
    return lib is not None and hasattr(lib, "hp_hot_begin")


def lease_available() -> bool:
    """True when the loaded library exports the quota-lease symbols (an
    old pre-stamped binary without them serves without the lease tier)."""
    lib = _load()
    return lib is not None and hasattr(lib, "hp_lease_grant")


def pod_available() -> bool:
    """True when the loaded library exports the pod ownership mirror
    (an old pre-stamped binary without it cannot serve the shard-aware
    hot lane — pod mode then falls back to the routed compiled plane)."""
    lib = _load()
    return lib is not None and hasattr(lib, "hp_pod_config")


def pod_hash(data: bytes) -> int:
    """The C-side crc32 over raw bytes (== zlib.crc32 — the parity-fuzz
    anchor for routing.stable_hash's mirror)."""
    lib = _load()
    if lib is None or not hasattr(lib, "hp_pod_hash"):
        raise RuntimeError("native pod ownership mirror unavailable")
    return lib.hp_pod_hash(data, len(data))


def loaded():
    """The library WITHOUT triggering a build (optional fast paths that
    must never stall a serving process on a first-use compile)."""
    lib = _LIB.peek()
    if lib is not None and not _sigs_done:
        return _load()
    return lib


def build_error() -> Optional[str]:
    _load()
    return _LIB.build_error


def partition_positions(group_ids: np.ndarray, n_groups: int):
    """Native grouped cumcount (one O(n) pass, GIL released); None when
    the library is not already loaded — callers keep the numpy path."""
    lib = loaded()
    if lib is None or not hasattr(lib, "hp_partition_positions"):
        return None
    group_ids = np.ascontiguousarray(group_ids, np.int32)
    n = group_ids.shape[0]
    counts = np.empty(n_groups, np.int64)
    pos = np.empty(n, np.int64)
    lib.hp_partition_positions(
        group_ids.ctypes.data, n, n_groups, counts.ctypes.data,
        pos.ctypes.data,
    )
    return counts, pos


# -- native telemetry plane (ISSUE 7) ----------------------------------------
# Process-global in the C library (NULL-ctx finishes and interner-recycle
# context swaps both demand it), so these are module functions, not
# HostPath methods. All calls are GIL-free and wait-free on the C side.
# Like the ingress bindings, these PEEK at the library: arming telemetry
# for a server that never uses the native lane must not stall startup on
# a first-use compile — ``_load`` re-arms the desired state the moment
# something else builds/loads the library for real.

_tel_desired = None  # (enabled, slow_row_ns, trace_sample) or None


def _peek_lib():
    lib = _LIB.peek()
    if lib is not None and not _sigs_done:
        return _load()  # already dlopened: binding signatures is cheap
    return lib


def tel_available() -> bool:
    """True when the library is LOADED and exports the telemetry plane
    (an old pre-stamped binary without it serves untelemetered; an
    unloaded library reports False rather than compiling)."""
    lib = _peek_lib()
    return lib is not None and hasattr(lib, "hp_tel_drain")


def tel_config(enabled: bool, slow_row_ns: int = 0,
               trace_sample: int = 0) -> bool:
    """Arm (or disarm) the native telemetry plane: histogram observes,
    the slow-row exemplar threshold (per-row average ns; 0 = exemplars
    off) and 1-in-N begin trace sampling (0 = off). The desired state
    is remembered and applied on library load when the library isn't
    live yet; returns False in that case."""
    global _tel_desired
    _tel_desired = (1 if enabled else 0, int(slow_row_ns),
                    int(trace_sample))
    if not tel_available():
        return False
    _peek_lib().hp_tel_config(*_tel_desired)
    return True


def tel_drain() -> Dict[str, dict]:
    """Cumulative native phase histograms:
    ``{phase: {"count", "sum_ns", "buckets": [TEL_BUCKETS]}}``. One
    GIL-free C call; {} when the library is not loaded or lacks the
    telemetry plane. The
    layout size is echoed by the C side — a constants mismatch (stale
    binding vs rebuilt library) raises instead of misparsing."""
    if not tel_available():
        return {}
    stride = 2 + TEL_BUCKETS
    out = np.zeros(len(TEL_PHASES) * stride, np.int64)
    need = _peek_lib().hp_tel_drain(out.ctypes.data, out.shape[0])
    if need != out.shape[0]:
        raise RuntimeError(
            f"hp_tel_drain layout mismatch: library says {need} int64s, "
            f"binding allocated {out.shape[0]}"
        )
    snap: Dict[str, dict] = {}
    for i, phase in enumerate(TEL_PHASES):
        rec = out[i * stride:(i + 1) * stride]
        snap[phase] = {
            "count": int(rec[0]),
            "sum_ns": int(rec[1]),
            "buckets": rec[2:].tolist(),
        }
    return snap


def tel_exemplars(cap: int = 64) -> List[dict]:
    """Drain (and clear) the slow-row exemplar ring: one dict per slow
    begin, oldest first."""
    if not tel_available():
        return []
    out = np.zeros((max(int(cap), 1), TEL_EX_STRIDE), np.int64)
    n = _peek_lib().hp_tel_exemplars(out.ctypes.data, out.shape[0])
    keys = ("total_ns", "lookup_ns", "stage_ns", "rows", "kernel_rows",
            "staged_hits", "miss_rows", "leased_rows", "blob_digest",
            "blob_len", "plan_kind", "lease_tokens")
    return [dict(zip(keys, row)) for row in out[:n].tolist()]


class HostPath:
    """One native context: interner + tracked keys + slot map + plan
    mirror."""

    def __init__(self, tracked_keys: Sequence[str] = ()):
        lib = _load()
        if lib is None:
            raise RuntimeError(f"native hostpath unavailable: {_LIB.build_error}")
        self._lib = lib
        self._ctx = ctypes.c_void_p(lib.hp_new())
        self.tracked: List[str] = []
        for key in tracked_keys:
            self.track(key)

    def close(self) -> None:
        if self._ctx:
            self._lib.hp_free(self._ctx)
            self._ctx = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    def track(self, key: str) -> int:
        raw = key.encode()
        idx = self._lib.hp_track_key(self._ctx, raw, len(raw))
        self.tracked.append(key)
        return idx

    def intern(self, s: str) -> int:
        raw = s.encode()
        return self._lib.hp_intern(self._ctx, raw, len(raw))

    def find(self, s: str) -> int:
        raw = s.encode()
        return self._lib.hp_find(self._ctx, raw, len(raw))

    def string(self, token: int) -> str:
        if not self._ctx:
            raise KeyError(token)  # context closed (interner recycle)
        out = ctypes.c_char_p()
        n = self._lib.hp_string(self._ctx, token, ctypes.byref(out))
        if n < 0:
            raise KeyError(token)
        return ctypes.string_at(out, n).decode()

    def interned_count(self) -> int:
        return self._lib.hp_interned_count(self._ctx)

    def parse_batch(
        self, blobs: Sequence[bytes]
    ) -> Tuple[np.ndarray, np.ndarray, Dict[str, np.ndarray], np.ndarray, np.ndarray]:
        """Parse serialized RateLimitRequest blobs into columns.

        Returns (domain_tokens, hits, columns{key->tokens}, ndesc_entries,
        extra_descriptors); -1 marks absent/failed."""
        n = len(blobs)
        # fromiter(map(len,...)) skips the intermediate list — this line
        # runs per batch on the serving hot path
        sizes = np.fromiter(map(len, blobs), np.int32, count=n)
        buf = b"".join(blobs)
        domains = np.empty(n, np.int32)
        hits = np.empty(n, np.int32)
        cols = np.empty((max(len(self.tracked), 1), n), np.int32)
        ndesc = np.empty(n, np.int32)
        extra = np.empty(n, np.int32)
        self._lib.hp_parse_batch(
            self._ctx, buf, sizes, n, domains, hits, cols, ndesc, extra
        )
        columns = {
            key: cols[i] for i, key in enumerate(self.tracked)
        }
        return domains, hits, columns, ndesc, extra

    def as_interner(self) -> "NativeInterner":
        return NativeInterner(self)

    def hot_lane(self, scratch_slot: int, cap: int = 1 << 16,
                 max_rows: int = 1 << 15) -> "NativeHotLane":
        return NativeHotLane(self, scratch_slot, cap, max_rows)

    # -- plan mirror ---------------------------------------------------------

    def plan_count(self) -> int:
        if not self._ctx:
            return 0  # context closed (interner recycle)
        return self._lib.hp_plan_count(self._ctx)

    def lane_stats(self) -> dict:
        out = np.zeros(9, np.int64)
        if self._ctx:  # zeros after close (interner recycle)
            self._lib.hp_lane_stats(self._ctx, out.ctypes.data)
        keys = ("hits", "misses", "staged_hits", "insertions",
                "invalidations", "overflows", "plans", "epoch", "foreign")
        return dict(zip(keys, out.tolist()))

    def plan_export(self) -> list:
        """Snapshot every live mirror entry (ISSUE 18 plan-seed lane).

        Tokens in the C table (ns_token, the rec name column) are THIS
        process's interner values, and device slots are host-local — so
        the snapshot resolves both to strings here and ships {blob,
        kind, ns, delta, delta_capped, owner, hits:[{slot, max,
        window_ms, bucket, name}]}. An importer replays entries through
        NativeHotLane.plan_put with its own tokens/slots; a raw byte
        copy between processes would alias unrelated strings."""
        if not self._ctx or not hasattr(self._lib, "hp_plan_export"):
            return []
        need = self._lib.hp_plan_export(self._ctx, None, 0)
        if need <= 0:
            return []
        buf = (ctypes.c_uint8 * need)()
        got = self._lib.hp_plan_export(self._ctx, buf, need)
        if got <= 0 or got > need:
            return []  # mirror grew between probe and copy; skip seed
        raw = bytes(buf[:got])
        (count,) = struct.unpack_from("<q", raw, 0)
        off = 8
        out = []
        for _ in range(count):
            (blob_len,) = struct.unpack_from("<i", raw, off)
            off += 4
            blob = raw[off:off + blob_len]
            off += blob_len
            kind, ns_token, delta, delta_capped, owner, nhits = (
                struct.unpack_from("<6i", raw, off)
            )
            off += 24
            hits = []
            for _h in range(nhits):
                slot, mx, window_ms, bucket, name_token = (
                    struct.unpack_from("<5i", raw, off)
                )
                off += 20
                try:
                    name = self.string(name_token) if name_token >= 0 else None
                except KeyError:
                    name = None
                hits.append({"slot": slot, "max": mx,
                             "window_ms": window_ms, "bucket": bucket,
                             "name": name})
            try:
                ns = self.string(ns_token) if ns_token >= 0 else None
            except KeyError:
                ns = None
            out.append({"blob": blob, "kind": kind, "ns": ns,
                        "delta": delta, "delta_capped": delta_capped,
                        "owner": owner, "hits": hits})
        return out

    # -- pod ownership mirror (ISSUE 13) -------------------------------------

    def pod_config(self, hosts: int, host_id: int,
                   shards_per_host: int) -> None:
        """Arm the foreign split: begins classify plans stamped with a
        non-local owner as LANE_FOREIGN_BASE + owner instead of staging
        them. hosts <= 1 keeps the single-host posture byte-identical.
        Raises when the topology exceeds the int8 lane-code encoding
        (owner > 127 - LANE_FOREIGN_BASE); callers fall back to the
        routed compiled plane rather than mis-route."""
        rc = self._lib.hp_pod_config(
            self._ctx, int(hosts), int(host_id), int(shards_per_host)
        )
        if rc != 0:
            raise RuntimeError(
                f"pod topology of {hosts} hosts exceeds the native "
                "lane's int8 owner encoding (max "
                f"{128 - LANE_FOREIGN_BASE} hosts)"
            )

    def pod_owner(self, key_repr: bytes) -> int:
        """Owner host of one counter key's repr bytes under the armed
        topology (== routing.PodTopology.owner_host; parity-fuzzed)."""
        return self._lib.hp_pod_owner(self._ctx, key_repr, len(key_repr))

    # -- slot map -----------------------------------------------------------

    def slots_lookup(self, keys: np.ndarray) -> np.ndarray:
        keys = np.ascontiguousarray(keys, np.int32)
        n, k = keys.shape
        out = np.empty(n, np.int64)
        self._lib.hp_slots_lookup(self._ctx, keys, n, k, out)
        return out

    def slots_insert(self, key: np.ndarray, slot: int) -> None:
        key = np.ascontiguousarray(key, np.int32)
        self._lib.hp_slots_insert(self._ctx, key, key.shape[0], slot)

    def slots_remove(self, key: np.ndarray) -> None:
        key = np.ascontiguousarray(key, np.int32)
        self._lib.hp_slots_remove(self._ctx, key, key.shape[0])

    def slots_count(self) -> int:
        return self._lib.hp_slots_count(self._ctx)


class HotStaged:
    """One hot begin's outputs: the response-code column, the staged
    kernel geometry, and the per-kernel-row metadata the finish pass
    needs. ``codes`` / per-row arrays are owned copies (the lane's
    scratch is reused by the next begin), and so are the staging
    columns the kernel launch takes (``NativeHotLane.kernel_columns``)."""

    __slots__ = (
        "codes", "k", "nhits", "H", "rows", "row_nhits", "row_delta",
        "row_ns", "hit_names", "ok_aggr", "fill_results", "leased_rows",
        "lookup_ns", "stage_ns", "trace_id", "foreign_rows",
    )

    def __init__(self, codes, k, nhits, H, rows, row_nhits, row_delta,
                 row_ns, hit_names, ok_aggr, leased_rows=0, lookup_ns=0,
                 stage_ns=0, trace_id=0, foreign_rows=0):
        self.codes = codes
        self.k = k
        self.nhits = nhits
        self.H = H
        self.rows = rows
        self.row_nhits = row_nhits
        self.row_delta = row_delta
        self.row_ns = row_ns
        self.hit_names = hit_names
        self.ok_aggr = ok_aggr  # [(ns_token, calls, hits)] at begin time
        self.fill_results = True
        # telemetry tail (zeros with the native plane off): the begin's
        # native phase splits, leased-row count, and the 1-in-N sampled
        # trace id (0 = unsampled) for OTLP span attachment
        self.leased_rows = leased_rows
        self.lookup_ns = lookup_ns
        self.stage_ns = stage_ns
        self.trace_id = trace_id
        #: rows classified foreign-owned (codes >= LANE_FOREIGN_BASE) —
        #: zero means the caller may skip the bulk-forward scan entirely
        self.foreign_rows = foreign_rows


def staged_trace_attrs(staged: "HotStaged") -> dict:
    """OTLP span attributes for a 1-in-N sampled hot-lane begin: the
    trace id the C side stamped plus the native phase splits. ONE
    schema shared by the submit-flush and ingress span legs — callers
    gate on ``staged.trace_id`` first."""
    return {
        "native.trace_id": int(staged.trace_id),
        "native.hot_lookup_ms": round(staged.lookup_ns / 1e6, 4),
        "native.hot_stage_ms": round(staged.stage_ns / 1e6, 4),
        "native.leased_rows": int(staged.leased_rows),
    }


class NativeHotLane:
    """Pre-allocated staging + scratch for the C hot lane of ONE
    HostPath context. Not thread-safe by itself: callers serialize
    begins under the pipeline's native lock (finish is stateless in C
    and touches only per-call copies)."""

    def __init__(self, hp: HostPath, scratch_slot: int, cap: int = 1 << 16,
                 max_rows: int = 1 << 15):
        self.hp = hp
        self._lib = hp._lib
        self._ctx = hp._ctx
        self.scratch_slot = int(scratch_slot)
        # pow2 capacity: the C side pads to the kernel's pow2 bucket in
        # place, so H <= cap must always hold
        c = 8
        while c < cap:
            c <<= 1
        self.cap = c
        # kernel staging columns (uploaded via begin_check_columnar)
        self.slots = np.empty(c, np.int32)
        self.deltas = np.empty(c, np.int32)
        self.maxes = np.empty(c, np.int32)
        self.windows = np.empty(c, np.int32)
        self.req = np.empty(c, np.int32)
        self.bucket = np.zeros(c, bool)
        # cached slots are live, never fresh: one immutable all-False
        # column shared by every launch
        self.fresh = np.zeros(c, bool)
        self._hit_names = np.empty(c, np.int32)
        self._resize_rows(max_rows)
        # 8 geometry slots + the 4-slot telemetry tail (hp_hot_begin
        # writes all 12 every call; zeros with the plane off)
        self._meta = np.zeros(12, np.int64)
        # token -> namespace / limit-name string memos (metrics apply)
        self._ns_strings: Dict[int, str] = {}
        self._name_strings: Dict[int, Optional[str]] = {}

    def _resize_rows(self, n: int) -> None:
        self.max_rows = n
        self._kind = np.empty(n, np.int8)
        self._rows = np.empty(n, np.int32)
        self._row_nhits = np.empty(n, np.int32)
        self._row_delta = np.empty(n, np.int32)
        self._row_ns = np.empty(n, np.int32)
        self._ok_ns = np.empty(n, np.int32)
        self._ok_calls = np.empty(n, np.int64)
        self._ok_hits = np.empty(n, np.int64)
        self._lim_ns = np.empty(n, np.int32)
        self._lim_name = np.empty(n, np.int32)
        self._lim_count = np.empty(n, np.int64)
        self._counts = np.zeros(2, np.int64)

    # -- mirror management ---------------------------------------------------

    def sync_epoch(self, epoch: int) -> None:
        self._lib.hp_plan_epoch(self._ctx, epoch)

    def invalidate_slot(self, slot: int) -> None:
        self._lib.hp_plan_invalidate_slot(self._ctx, slot)

    def plan_put(self, blob: bytes, epoch: int, kind: int, ns_token: int,
                 delta: int, delta_capped: int,
                 rec: Optional[np.ndarray] = None,
                 ns: Optional[str] = None, names=()) -> None:
        """Mirror one derived plan; ``rec`` is int32 (nhits, 5):
        slot, max, window_ms, bucket, name token. ``ns``/``names`` seed
        the token->string memos so the finish pass (metrics apply) never
        needs the interner — which may belong to an already-recycled
        context by then."""
        if ns is not None:
            self._ns_strings[ns_token] = ns
        for token, name in names:
            if token >= 0:
                self._name_strings[token] = name
        if rec is None:
            ptr, nhits = None, 0
        else:
            rec = np.ascontiguousarray(rec, np.int32)
            ptr, nhits = rec.ctypes.data, rec.shape[0]
        self._lib.hp_plan_put(
            self._ctx, blob, len(blob), epoch, kind, ns_token,
            min(int(delta), _INT32_MAX), int(delta_capped), ptr, nhits,
        )

    # -- pod ownership stamps (ISSUE 13) -------------------------------------
    # Called right after plan_put on the miss path, under the same
    # native+storage locks as the begins that read the stamp.

    def plan_stamp_owner(self, blob: bytes, epoch: int,
                         key_repr: bytes) -> int:
        """Stamp the plan with the owner of its single counter key —
        the crc32 verdict computed IN C from the key's repr bytes.
        Returns the owner, or -1 when the plan is gone / epoch moved."""
        return self._lib.hp_plan_stamp_owner(
            self._ctx, blob, len(blob), epoch, key_repr, len(key_repr)
        )

    def plan_set_owner(self, blob: bytes, epoch: int, owner: int) -> bool:
        """Stamp a pre-resolved owner (pinned namespace / multi-key
        router verdict); owner < 0 clears the stamp (locally owned)."""
        return bool(self._lib.hp_plan_set_owner(
            self._ctx, blob, len(blob), epoch, int(owner)
        ))

    # -- quota leasing (lease/broker.py) -------------------------------------
    # All lease calls run under the pipeline's native lock, the same lock
    # serializing the begins that consume tokens.

    def lease_config(self, enabled: bool, hot_threshold: int = 8) -> None:
        if hasattr(self._lib, "hp_lease_config"):
            self._lib.hp_lease_config(
                self._ctx, 1 if enabled else 0, int(hot_threshold)
            )

    def lease_grant(self, blob: bytes, epoch: int, lease_id: int,
                    tokens: int) -> bool:
        """Attach a pre-debited grant to the mirrored plan; False means
        the plan is gone / epoch moved / already leased — the caller
        must credit the debit straight back."""
        return bool(self._lib.hp_lease_grant(
            self._ctx, blob, len(blob), epoch, lease_id, int(tokens)
        ))

    def lease_revoke(self, blob: bytes, expect_id: int = -1) -> int:
        """Reclaim a lease synchronously; returns the remaining tokens,
        or -1 when there is nothing live to reclaim (the tokens already
        travelled through the return ring, the plan is gone, or the
        plan's live lease is a newer grant than ``expect_id``)."""
        return self._lib.hp_lease_revoke(
            self._ctx, blob, len(blob), expect_id
        )

    def lease_tokens(self, blob: bytes, expect_id: int = -1) -> int:
        return self._lib.hp_lease_tokens(
            self._ctx, blob, len(blob), expect_id
        )

    def lease_drain_returns(self, cap: int = 4096):
        """[(lease_id, stranded tokens)] pushed by invalidation/clear."""
        ids = np.empty(cap, np.int64)
        tokens = np.empty(cap, np.int64)
        n = self._lib.hp_lease_drain_returns(
            self._ctx, ids.ctypes.data, tokens.ctypes.data, cap
        )
        return list(zip(ids[:n].tolist(), tokens[:n].tolist()))

    def lease_candidates(self, cap: int = 256, blob_cap: int = 1 << 20):
        """[(blob bytes, observed demand)] for hot unleased kernel
        plans; draining resets their demand counts."""
        blobs = np.empty(blob_cap, np.uint8)
        lens = np.empty(cap, np.int32)
        counts = np.empty(cap, np.int64)
        n = self._lib.hp_lease_candidates(
            self._ctx, blobs.ctypes.data, blob_cap, lens.ctypes.data,
            counts.ctypes.data, cap,
        )
        if n == 0:
            return []
        used = int(lens[:n].sum())
        raw = blobs[:used].tobytes()  # copy only the written prefix
        out = []
        off = 0
        for i in range(n):
            ln = int(lens[i])
            out.append((raw[off:off + ln], int(counts[i])))
            off += ln
        return out

    def lease_stats(self) -> dict:
        out = np.zeros(8, np.int64)
        if self._ctx and hasattr(self._lib, "hp_lease_stats"):
            self._lib.hp_lease_stats(self._ctx, out.ctypes.data)
        keys = ("leased", "grants", "granted_tokens", "ring_tokens",
                "active", "outstanding", "pending_candidates",
                "pending_returns")
        return dict(zip(keys, out.tolist()))

    def usage_drain(self, cap: int = 1024, blob_cap: int = 1 << 20):
        """[(blob bytes, leased admissions since last drain)] — the
        native half of the tenant usage observatory. Leased rows never
        reach the device's per-slot hit accumulator; the observatory
        resolves each blob to its plan's slots and merges these counts
        in. Draining resets the per-plan counts; plans that don't fit
        the buffers keep theirs for the next drain."""
        if not self._ctx or not hasattr(self._lib, "hp_usage_drain"):
            return []
        blobs = np.empty(blob_cap, np.uint8)
        lens = np.empty(cap, np.int32)
        counts = np.empty(cap, np.int64)
        n = self._lib.hp_usage_drain(
            self._ctx, blobs.ctypes.data, blob_cap, lens.ctypes.data,
            counts.ctypes.data, cap,
        )
        if n == 0:
            return []
        used = int(lens[:n].sum())
        raw = blobs[:used].tobytes()
        out = []
        off = 0
        for i in range(n):
            ln = int(lens[i])
            out.append((raw[off:off + ln], int(counts[i])))
            off += ln
        return out

    # -- begin / finish ------------------------------------------------------

    def begin_ptrs(self, ptrs, lens, n: int, epoch: int) -> HotStaged:
        """The zero-copy begin: ``ptrs``/``lens`` address the blobs in
        place (the ingress's take buffers, or a ctypes view over Python
        bytes). One GIL-free C call: plan lookup, columnar staging,
        padding, begin-time codes and OK-metric aggregation."""
        if n > self.max_rows:
            self._resize_rows(max(n, self.max_rows * 2))
        k = self._lib.hp_hot_begin(
            self._ctx,
            ctypes.addressof(ptrs) if not isinstance(ptrs, int) else ptrs,
            ctypes.addressof(lens) if not isinstance(lens, int) else lens,
            n, epoch,
            self._kind.ctypes.data, self.slots.ctypes.data,
            self.deltas.ctypes.data, self.maxes.ctypes.data,
            self.windows.ctypes.data, self.req.ctypes.data,
            self.bucket.ctypes.data, self.cap, self.scratch_slot,
            self._rows.ctypes.data, self._row_nhits.ctypes.data,
            self._row_delta.ctypes.data, self._row_ns.ctypes.data,
            self._hit_names.ctypes.data, self._ok_ns.ctypes.data,
            self._ok_calls.ctypes.data, self._ok_hits.ctypes.data,
            self._meta.ctypes.data,
        )
        return self._staged_from_scratch(n, k)

    def begin(self, blobs: Sequence[bytes], epoch: int) -> HotStaged:
        """Begin over a list of bytes objects, via one join (the
        pointer table is derived in C — building it through ctypes
        costs ~850ns/row, 4x the whole C pass)."""
        n = len(blobs)
        if n > self.max_rows:
            self._resize_rows(max(n, self.max_rows * 2))
        sizes = np.fromiter(map(len, blobs), np.int32, count=n)
        buf = b"".join(blobs)
        k = self._lib.hp_hot_begin_buf(
            self._ctx, buf, sizes.ctypes.data, n, epoch,
            self._kind.ctypes.data, self.slots.ctypes.data,
            self.deltas.ctypes.data, self.maxes.ctypes.data,
            self.windows.ctypes.data, self.req.ctypes.data,
            self.bucket.ctypes.data, self.cap, self.scratch_slot,
            self._rows.ctypes.data, self._row_nhits.ctypes.data,
            self._row_delta.ctypes.data, self._row_ns.ctypes.data,
            self._hit_names.ctypes.data, self._ok_ns.ctypes.data,
            self._ok_calls.ctypes.data, self._ok_hits.ctypes.data,
            self._meta.ctypes.data,
        )
        return self._staged_from_scratch(n, k)

    def _staged_from_scratch(self, n: int, k: int) -> HotStaged:
        meta = self._meta
        nhits, H = int(meta[1]), int(meta[2])
        n_ok = int(meta[6])
        ok_aggr = (
            list(zip(self._ok_ns[:n_ok].tolist(),
                     self._ok_calls[:n_ok].tolist(),
                     self._ok_hits[:n_ok].tolist()))
            if n_ok else []
        )
        return HotStaged(
            self._kind[:n].copy(), k, nhits, H,
            self._rows[:k].copy(), self._row_nhits[:k].copy(),
            self._row_delta[:k].copy(), self._row_ns[:k].copy(),
            self._hit_names[:nhits].copy(), ok_aggr,
            leased_rows=int(meta[10]), lookup_ns=int(meta[8]),
            stage_ns=int(meta[9]), trace_id=int(meta[11]),
            foreign_rows=int(meta[7]),
        )

    def kernel_columns(self, H: int):
        """The staged columns for ``begin_check_columnar``, as owned
        copies. A launch does NOT consume its host arguments before it
        returns: the CPU backend aliases an aligned numpy buffer
        outright and an accelerator may read it until the transfer
        completes, so handing it views of the staging buffers lets the
        next begin rewrite a batch the device has not read yet (seen as
        hot keys over-admitted whenever a launch lagged, e.g. behind a
        compile).
        ``fresh`` is never written and stays a view."""
        return (
            self.slots[:H].copy(), self.deltas[:H].copy(),
            self.maxes[:H].copy(), self.windows[:H].copy(),
            self.req[:H].copy(), self.fresh[:H], self.bucket[:H].copy(),
        )

    def finish(self, staged: HotStaged, admitted, hit_ok):
        """Turn the device result columns into final response codes
        (in-place on ``staged.codes``) and return the batch's aggregated
        metrics: ([(ns, calls, hits)], [(ns, name|None, count)])."""
        k, nhits = staged.k, staged.nhits
        adm = np.ascontiguousarray(admitted[:k], np.uint8)
        hok = np.ascontiguousarray(hit_ok[:nhits], np.uint8)
        # Per-call scratch: finish runs on collect threads concurrently
        # with the next begin (which owns the lane's shared scratch) and
        # with other finishes. The C pass is context-free — NULL ctx, so
        # a pending that outlives an interner-recycle context swap (the
        # old HostPath is closed) still finishes safely.
        ok_ns = np.empty(max(k, 1), np.int32)
        ok_calls = np.empty(max(k, 1), np.int64)
        ok_hits = np.empty(max(k, 1), np.int64)
        lim_ns = np.empty(max(k, 1), np.int32)
        lim_name = np.empty(max(k, 1), np.int32)
        lim_count = np.empty(max(k, 1), np.int64)
        counts = np.zeros(2, np.int64)
        self._lib.hp_hot_finish(
            None, adm.ctypes.data, hok.ctypes.data, k,
            staged.rows.ctypes.data, staged.row_nhits.ctypes.data,
            staged.row_delta.ctypes.data, staged.row_ns.ctypes.data,
            staged.hit_names.ctypes.data, staged.codes.ctypes.data,
            ok_ns.ctypes.data, ok_calls.ctypes.data,
            ok_hits.ctypes.data, lim_ns.ctypes.data,
            lim_name.ctypes.data, lim_count.ctypes.data,
            counts.ctypes.data,
        )
        n_ok, n_lim = int(counts[0]), int(counts[1])
        ok = [
            (self._ns_string(ns), calls, hits)
            for ns, calls, hits in zip(
                ok_ns[:n_ok].tolist(), ok_calls[:n_ok].tolist(),
                ok_hits[:n_ok].tolist(),
            )
        ]
        limited = [
            (self._ns_string(ns), self._name_string(name), count)
            for ns, name, count in zip(
                lim_ns[:n_lim].tolist(), lim_name[:n_lim].tolist(),
                lim_count[:n_lim].tolist(),
            )
        ]
        return ok, limited

    def ok_aggr_strings(self, ok_aggr):
        """Begin-time OK aggregation with namespace tokens resolved."""
        return [
            (self._ns_string(ns), calls, hits)
            for ns, calls, hits in ok_aggr
        ]

    def _ns_string(self, token: int) -> str:
        s = self._ns_strings.get(token)
        if s is None:
            s = self.hp.string(token)
            self._ns_strings[token] = s
        return s

    def _name_string(self, token: int) -> Optional[str]:
        if token < 0:
            return None
        s = self._name_strings.get(token)
        if s is None:
            s = self.hp.string(token)
            self._name_strings[token] = s
        return s

    def stats(self) -> dict:
        return self.hp.lane_stats()


class _IdsView:
    """dict-like `.get` over the native interner (compiled-constant lookup
    interface the mask programs use)."""

    __slots__ = ("hp",)

    def __init__(self, hp: HostPath):
        self.hp = hp

    def get(self, s: str, default: int = -2) -> int:
        out = self.hp.find(s)
        return out if out != -2 else default


class _StringsView:
    __slots__ = ("hp",)

    def __init__(self, hp: HostPath):
        self.hp = hp

    def __getitem__(self, token: int) -> str:
        return self.hp.string(token)


class NativeInterner:
    """Drop-in for compiler.Interner backed by the C++ table, so compiled
    constants and natively-parsed columns share one id space."""

    __slots__ = ("hp", "_ids", "strings")

    def __init__(self, hp: HostPath):
        self.hp = hp
        self._ids = _IdsView(hp)
        self.strings = _StringsView(hp)

    def intern(self, s: str) -> int:
        return self.hp.intern(s)

    def __len__(self) -> int:
        return self.hp.interned_count()
