"""TpuStorage — the device-resident counter backend.

Implements the ``CounterStorage`` protocol (storage/base.py, mirroring
/root/reference/limitador/src/storage/mod.rs:279-293) over the fused kernel
in limitador_tpu/ops/kernel.py. Equivalent of the reference's
``InMemoryStorage`` in exactness (never over-admits; check-all-then-
update-all) with counters living in device HBM instead of host maps:

- The host owns the key space: counter identity -> slot index, mirroring the
  reference's split between the unbounded simple-limits map
  (in_memory.rs:14) and the LRU-capped qualified-counter cache
  (in_memory.rs:15-16, 204-212). Qualified slots are evicted LRU (as moka's
  cap does); simple-limit slots are pinned.
- The device owns the values: a dense int32 (value, expiry_ms) table; every
  check/update is a fused gather -> admit -> scatter kernel call.
- ``check_many`` is the single implementation of hit-array construction,
  reference processing order, first-limited naming and the non-load
  early-return slot-release semantics; the per-call ``check_and_update``
  and the async MicroBatcher (tpu/batcher.py) both go through it.

Documented representation limits (see ops/kernel.py): max_value clamps to
2**30, deltas to 2**30-1, windows to WINDOW_MS_CAP (~12.4 days). The epoch
auto-rebases on long uptimes so expiries never overflow int32.
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from ..core.counter import Counter
from ..core.limit import Limit
from ..storage.base import (
    Authorization,
    CounterStorage,
    StorageError,
    require_nonnegative_delta,
)
from ..storage.expiring_value import ExpiringValue
from ..storage.gcra import (
    GcraValue,
    cell_for_limit,
    device_eligible,
    emission_interval_ms,
    restore_cell,
    spent_tokens,
)
from ..ops import kernel as K

__all__ = ["TpuStorage"]

_INT32_MAX = np.int32(np.iinfo(np.int32).max)


def _bucket(n: int, floor: int = 8) -> int:
    """Next power of two >= n (static kernel shapes, few XLA programs)."""
    b = floor
    while b < n:
        b <<= 1
    return b


def _clamp_window_ms(seconds: int) -> int:
    return min(seconds * 1000, K.WINDOW_MS_CAP)


def _staged(values, H: int, fill, dtype) -> np.ndarray:
    """Right-sized staging array: prefix from a Python list (one C-level
    conversion), padding filled with the inert default — replaces the
    ``np.asarray(list + [pad] * k)`` pattern that built a second
    H-element Python list per column per batch."""
    arr = np.empty(H, dtype)
    n = len(values)
    if n:
        arr[:n] = values
    if n < H:
        arr[n:] = fill
    return arr


def _native_partition(group_ids: np.ndarray, n_groups: int):
    """Native (GIL-free, O(n), no argsort) grouped cumcount when the
    hostpath library is ALREADY loaded — never triggers a first-use
    compile from a staging pass. Returns None to keep the numpy path."""
    try:
        from .. import native
    except Exception:  # pragma: no cover - import cycles in odd embeddings
        return None
    try:
        return native.partition_positions(group_ids, n_groups)
    except Exception:
        return None


def _partition_positions(
    group_ids: np.ndarray, n_groups: int
) -> Tuple[np.ndarray, np.ndarray]:
    """Vectorized grouped cumcount: for flat staged rows labeled with a
    group (a shard id, a request's home shard), return
    ``(counts[n_groups], pos)`` where ``pos[i]`` is row i's index WITHIN
    its group, counted in input order. This is the host side of the
    sharded partition step, riding every MicroBatcher flush on sharded
    storage. Two implementations, identical outputs: the native one
    (one O(n) C pass, hostpath.cc ``hp_partition_positions``) when the
    library is already loaded, else one argsort + two cumsums — either
    way no per-row Python (tests/test_perf_smoke.py budgets it)."""
    m = group_ids.shape[0]
    if m >= 2048:
        native_out = _native_partition(group_ids, n_groups)
        if native_out is not None:
            return native_out
    counts = np.bincount(group_ids, minlength=n_groups)
    order = np.argsort(group_ids, kind="stable")
    starts = np.concatenate(([0], np.cumsum(counts)[:-1]))
    pos = np.empty(m, np.int64)
    pos[order] = np.arange(m, dtype=np.int64) - np.repeat(starts, counts)
    return counts, pos


def _scatter_rows(
    shard_ids: np.ndarray,
    pos: np.ndarray,
    n: int,
    H: int,
    columns: Sequence[Tuple[Sequence, object, type]],
) -> List[np.ndarray]:
    """Scatter flat hit columns into per-shard ``[n, H]`` staging arrays
    (``(values, fill, dtype)`` per column) — one fancy-index store per
    column, pad rows pre-filled with the inert default. Flat order is
    request order, and ``pos`` counts per shard in flat order, so each
    shard's rows stay in request order (the kernel's nondecreasing
    req_ids contract)."""
    out = []
    for values, fill, dtype in columns:
        arr = np.full((n, H), fill, dtype)
        arr[shard_ids, pos] = values
        out.append(arr)
    return out


def hot_attribution(counter: Counter, value: int, ttl_ms: int) -> dict:
    """Tenant-usage attribution fields for one drained heavy-hitter slot
    (ISSUE 8): full slot->counter identity plus the utilization sample
    read at drain time. Shared by the single-chip and sharded drains.
    ``value`` is the raw values-lane read; bucket counters derive spent
    tokens from the ttl lane instead (their values lane is
    unspecified)."""
    limit = counter.limit
    if limit.policy == "token_bucket":
        value = spent_tokens(
            counter.max_value, counter.window_seconds, ttl_ms
        )
    max_value = int(counter.max_value)
    util = value / max_value if max_value > 0 else 0.0
    return {
        "namespace": str(counter.namespace),
        "limit_name": limit.name,
        "policy": limit.policy,
        "max_value": max_value,
        "seconds": counter.window_seconds,
        "key": dict(counter.set_variables),
        "value": int(value),
        # Unclamped on purpose: >1.0 is real signal (Report-role
        # unconditional updates can push past max_value).
        "utilization": round(util, 4),
        "ttl_s": round(ttl_ms / 1000.0, 3),
    }


def _hit_lane(counter: Counter) -> Tuple[int, bool]:
    """Per-hit (windows_ms lane, bucket flag) for a device-eligible
    counter: the window for fixed windows, the GCRA emission interval
    for token buckets (ops/kernel.py bucket lane)."""
    limit = counter.limit
    if limit.policy == "token_bucket":
        return emission_interval_ms(limit.max_value, limit.seconds), True
    return _clamp_window_ms(counter.window_seconds), False


def _migrate_key(key):
    """Pre-policy checkpoints: limit identity was a 4-tuple
    (ns, seconds, conditions, variables); current lookups build 5-tuples
    ending in the policy. Old keys are fixed-window."""
    if (
        isinstance(key, tuple) and len(key) == 2
        and isinstance(key[0], tuple) and len(key[0]) == 4
    ):
        return (key[0] + ("fixed_window",), key[1])
    return key


class _SlotTable:
    """Host-side key space: counter identity -> device slot."""

    def __init__(self, capacity: int):
        self.capacity = capacity
        self.free: List[int] = list(range(capacity - 1, -1, -1))
        # pinned (simple-limit) slots: key -> slot
        self.simple: Dict[tuple, int] = {}
        # LRU for qualified counters: key -> slot (front = oldest)
        self.qualified: "OrderedDict[tuple, int]" = OrderedDict()
        # slot -> (key, Counter identity object) for introspection
        self.info: Dict[int, Tuple[tuple, Counter]] = {}
        # slot -> native composite key + removal hook (native fast path)
        self.native_keys: Dict[int, object] = {}
        self.on_native_release = None
        # Decision-plan cache coherence (tpu/plan_cache.py): every slot
        # release fires on_slot_release(slot) so cached plans pinning the
        # slot are dropped before it can be recycled; wholesale table
        # swaps (clear/snapshot-restore) fire on_clear instead.
        self.on_slot_release = None
        self.on_clear = None
        # Device-plane telemetry (device_stats()): cumulative counts of
        # LRU evictions and of fresh allocations that recycled a
        # previously-occupied slot (the kernel's fresh flag overrides the
        # stale cell). Host bookkeeping only — never reset by dump/load.
        self.evictions = 0
        self.collisions = 0
        self._recycled: set = set()

    def lookup(self, key: tuple, qualified: bool) -> Optional[int]:
        if qualified:
            slot = self.qualified.get(key)
            if slot is not None:
                self.qualified.move_to_end(key)
            return slot
        return self.simple.get(key)

    def dump(self) -> dict:
        """Checkpoint form. The free list is NOT persisted (it would be
        O(capacity)); ``load`` derives it from the occupied set."""
        return {
            "simple": dict(self.simple),
            "qualified": list(self.qualified.items()),
            "info": dict(self.info),
        }

    def load(self, data: dict, lo: int, hi: int) -> None:
        """Restore from ``dump`` output; slots of this table live in
        [lo, hi)."""
        self.simple = {
            _migrate_key(k): v for k, v in dict(data["simple"]).items()
        }
        self.qualified.update(
            (_migrate_key(k), v) for k, v in data["qualified"]
        )
        self.info = {
            s: (_migrate_key(key), counter)
            for s, (key, counter) in dict(data["info"]).items()
        }
        if "free" in data:  # older checkpoints persisted the free list
            self.free = list(data["free"])
        else:
            occupied = set(self.info)
            self.free = [
                s for s in range(hi - 1, lo - 1, -1) if s not in occupied
            ]

    def alloc(self) -> int:
        """Pop a free slot; counts the recycled-slot collision when the
        slot held a (now released) counter before. Callers guarantee
        ``free`` is non-empty."""
        slot = self.free.pop()
        if slot in self._recycled:
            self._recycled.discard(slot)
            self.collisions += 1
        return slot

    def release(self, slot: int, key: tuple, qualified: bool) -> None:
        self.info.pop(slot, None)
        if qualified:
            self.qualified.pop(key, None)
        else:
            self.simple.pop(key, None)
        self.free.append(slot)
        self._recycled.add(slot)
        # Eviction coherence with the native slot map: a recycled slot must
        # not remain reachable under its old native key.
        native_key = self.native_keys.pop(slot, None)
        if native_key is not None and self.on_native_release is not None:
            self.on_native_release(native_key)
        if self.on_slot_release is not None:
            self.on_slot_release(slot)


class _BigLimitMixin:
    """Host-side exact counters for limits whose max_value exceeds the
    int32 device cap (the reference's max_value is u64, limit.rs:34).
    Shared by the single-chip and sharded storages; every method assumes
    the caller holds the storage lock.

    Admission projection (``_big_inflight``) spans in-flight batches: a
    hit admitted at begin time reserves its delta immediately, so a
    second pipelined batch can never over-admit against a stale value;
    the reservation is released (and the delta actually applied when the
    whole request was admitted) at finish."""

    def _init_big(self, cap: int) -> None:
        self._big: "OrderedDict[tuple, Tuple[ExpiringValue, Counter]]" = (
            OrderedDict()
        )
        self._big_inflight: Dict[tuple, int] = {}
        self._big_cap = max(int(cap), 1)
        # Per-limit routing memos: is-big and the (window_ms, bucket)
        # hit lane are pure functions of (limit identity, max_value),
        # re-derived on every hit before — the two getattr/compare
        # chains profiled in the host_stage phase. max_value is NOT part
        # of Limit identity (an update_limit may change only it), so it
        # rides in the key explicitly. Bounded: pruned wholesale past a
        # cap (limits registries are small; churn only comes from
        # reload loops).
        self._big_flags: Dict[tuple, bool] = {}
        self._lanes: Dict[tuple, Tuple[int, bool]] = {}

    def _is_big(self, counter: Counter) -> bool:
        # Token buckets run ON DEVICE (a TAT cell in the expiry lane,
        # ops/kernel.py bucket lane) whenever the int32-ms representation
        # fits; only finer-tick / beyond-cap buckets ride the exact host
        # path, same as beyond-cap fixed windows.
        limit = counter.limit
        key = (limit, limit.max_value)
        flag = self._big_flags.get(key)
        if flag is None:
            if limit.policy == "token_bucket":
                flag = not device_eligible(
                    counter.max_value, counter.window_seconds,
                    K.MAX_VALUE_CAP, K.WINDOW_MS_CAP,
                )
            else:
                flag = counter.max_value > K.MAX_VALUE_CAP
            if len(self._big_flags) >= 4096:
                self._big_flags.clear()
            self._big_flags[key] = flag
        return flag

    def _lane_of(self, counter: Counter) -> Tuple[int, bool]:
        """Memoized ``_hit_lane`` — per-(limit, max_value), not
        per-hit."""
        limit = counter.limit
        key = (limit, limit.max_value)
        lane = self._lanes.get(key)
        if lane is None:
            lane = _hit_lane(counter)
            if len(self._lanes) >= 4096:
                self._lanes.clear()
            self._lanes[key] = lane
        return lane

    def _big_cell(self, counter: Counter, key: tuple) -> ExpiringValue:
        entry = self._big.get(key)
        if entry is not None:
            self._big.move_to_end(key)
            return entry[0]
        cell = cell_for_limit(counter.limit)
        self._big[key] = (cell, counter.key())
        while len(self._big) > self._big_cap:
            evicted = False
            for k in self._big:
                if k != key and k not in self._big_inflight:
                    del self._big[k]
                    evicted = True
                    break
            if not evicted:
                break
        return cell

    def _big_remote_sum(self, key: tuple, now: float) -> int:
        """Live remote contribution to a big cell's admission base —
        0 here; the replicated topology overrides it with the gossiped
        per-actor sum (tpu/replicated.py)."""
        return 0

    def _on_big_write(self, key: tuple) -> None:
        """Hook: a big cell was locally incremented (caller holds the
        lock). The replicated topology queues it for gossip."""

    def _eval_big_hits(self, ordered, raw_delta: int, now: float):
        """First pass of a request: decide its big hits host-side.
        Returns (bigs, failed, projected) where each big is
        (j, ok, remaining, ttl_s, key, counter, delta) and projected lists
        (key, delta) reservations to release at finish."""
        bigs: list = []
        projected: List[Tuple[tuple, int]] = []
        failed = False
        for j, c in enumerate(ordered):
            if not self._is_big(c):
                continue
            key = self._key_of(c)
            cell = self._big_cell(c, key)
            value = (
                cell.value_at(now)
                + self._big_inflight.get(key, 0)
                + self._big_remote_sum(key, now)
            )
            ok = value + raw_delta <= c.max_value
            remaining = max(c.max_value - (value + raw_delta), 0)
            if isinstance(cell, GcraValue):
                # Token bucket: expires_in is time-to-full (0 = full);
                # there is no "fresh window" display case.
                ttl = cell.ttl(now)
            else:
                ttl = (
                    float(c.window_seconds)
                    if cell.is_expired(now) else cell.ttl(now)
                )
            bigs.append((j, ok, remaining, ttl, key, c, raw_delta))
            if ok:
                self._big_inflight[key] = (
                    self._big_inflight.get(key, 0) + raw_delta
                )
                projected.append((key, raw_delta))
            else:
                failed = True
        return bigs, failed, projected

    def _unproject_big(self, projected) -> None:
        for key, delta in projected:
            cur = self._big_inflight.get(key, 0) - delta
            if cur > 0:
                self._big_inflight[key] = cur
            else:
                self._big_inflight.pop(key, None)

    def _apply_big(self, applies, now: float) -> None:
        for key, delta, window in applies:
            entry = self._big.get(key)
            if entry is not None:
                entry[0].update(delta, window, now)
                self._on_big_write(key)

    def _emit_big_counters(self, limits, namespaces, now: float, out) -> None:
        for _key, (cell, counter) in self._big.items():
            if (
                counter.limit in limits
                or counter.namespace in namespaces
            ) and not cell.is_expired(now):
                c = counter.key()
                c.remaining = c.max_value - cell.value_at(now)
                c.expires_in = cell.ttl(now)
                out.add(c)

    def _delete_big(self, limits) -> None:
        for key, (_cell, counter) in list(self._big.items()):
            if counter.limit in limits:
                del self._big[key]

    def _clear_big(self) -> None:
        self._big.clear()
        self._big_inflight.clear()


class _Request:
    """One logical check inside a ``check_many`` batch."""

    __slots__ = ("ordered", "delta", "load")

    def __init__(self, counters: Sequence[Counter], delta: int, load: bool):
        # Reference processing order: simple counters then qualified
        # (in_memory.rs:104-139) — drives first_limited naming.
        self.ordered = [c for c in counters if not c.is_qualified()] + [
            c for c in counters if c.is_qualified()
        ]
        self.delta = delta
        self.load = load


class _CheckHandle:
    """In-flight batch: kernel launched, results not yet transferred.
    Produced by ``begin_check_many``, consumed by ``finish_check_many`` —
    the split lets the batcher dispatch batch N+1 while N's device->host
    transfer is still in flight (double buffering)."""

    __slots__ = ("requests", "fresh_hits_by_req", "slot_use_count",
                 "result", "seq", "watch_touches", "big_by_req",
                 "dev_info_by_req", "now", "big_projected")

    def __init__(self, requests, fresh_hits_by_req, slot_use_count, result,
                 seq, watch_touches, big_by_req, dev_info_by_req, now,
                 big_projected=()):
        self.requests = requests
        self.fresh_hits_by_req = fresh_hits_by_req
        self.slot_use_count = slot_use_count
        self.result = result
        self.seq = seq
        # Every slot whose _watched_slots entry this batch wrote; the
        # finish pass deletes the ones still carrying this batch's seq so
        # the watch map stays bounded by in-flight work.
        self.watch_touches = watch_touches
        # Host-side (max_value > device cap) hits, per request:
        # (j, ok, remaining, ttl_s, key, counter, delta).
        self.big_by_req = big_by_req
        # Device hits per request: (j, delta_adjust) in device-array order.
        self.dev_info_by_req = dev_info_by_req
        self.now = now
        # (key, delta) reservations in _big_inflight, released at finish.
        self.big_projected = big_projected


class TpuStorage(_BigLimitMixin, CounterStorage):
    supports_token_bucket = True  # via the exact host (big-limit) path

    def __init__(
        self,
        capacity: int = 1 << 20,
        cache_size: Optional[int] = None,
        clock=time.time,
    ):
        """``capacity`` sizes the device table (8 bytes/counter of HBM);
        ``cache_size`` caps qualified counters (default: capacity)."""
        self._lock = threading.RLock()
        self._clock = clock
        self._capacity = int(capacity)
        self._cache_size = int(cache_size) if cache_size else self._capacity
        self._table = _SlotTable(self._capacity)
        self._state = K.make_table(self._capacity)
        self._epoch = clock()  # device time 0 in host seconds
        self._scratch = self._capacity  # padding slot
        # Pipelining bookkeeping: batch sequence number + last-touch seq of
        # slots watched for deferred release (see finish_check_many).
        self._seq = 0
        self._watched_slots: Dict[int, int] = {}
        # Host-side fallback for limits whose max_value exceeds the int32
        # device cap: these counters never get a device slot (see
        # _BigLimitMixin); LRU-capped like the device's qualified cache.
        self._init_big(self._cache_size)

    # -- time --------------------------------------------------------------

    def _now_ms(self) -> int:
        now = int((self._clock() - self._epoch) * 1000)
        if now > (1 << 30):
            # Rebase before now_ms + WINDOW_MS_CAP could overflow int32.
            shift = now - 1000
            self._state = K.CounterTableState(
                self._state.values,
                K.rebase_epoch_chunked(self._state.expiry_ms, shift),
                self._state.hits,
            )
            self._epoch += shift / 1000.0
            now -= shift
        return now

    # -- slot management ---------------------------------------------------

    @staticmethod
    def _key_of(counter: Counter) -> tuple:
        # Counter._key() memoizes the identity tuple on the counter, so
        # reused counter objects (the compiled path's plan cache) stop
        # paying per-hit tuple construction + re-hash.
        return counter._key()

    def _evict_one(self) -> None:
        """Free the least-recently-used qualified slot (the moka cap
        analogue, in_memory.rs:204-212). Pure host bookkeeping: the recycled
        slot's stale device cell is overridden by the kernel's ``fresh``
        flag on next allocation — no device read or write here."""
        if not self._table.qualified:
            raise StorageError("TPU counter table full (no evictable slots)")
        key, slot = next(iter(self._table.qualified.items()))
        self._table.release(slot, key, qualified=True)
        self._table.evictions += 1

    def _slot_for(self, counter: Counter, create: bool) -> Tuple[Optional[int], bool]:
        """Return (slot, fresh). fresh=True when allocated/recycled now."""
        qualified = counter.is_qualified()
        key = self._key_of(counter)
        slot = self._table.lookup(key, qualified)
        if slot is not None:
            return slot, False
        if not create:
            return None, False
        if qualified:
            while len(self._table.qualified) >= self._cache_size:
                self._evict_one()
        if not self._table.free:
            self._evict_one()
        slot = self._table.alloc()
        if qualified:
            self._table.qualified[key] = slot
        else:
            self._table.simple[key] = slot
        self._table.info[slot] = (key, counter.key())
        return slot, True

    def device_stats(self) -> dict:
        """Device-plane table stats for /debug/stats and the per-shard
        Prometheus gauges (observability/device_plane.py): occupancy as a
        level, evictions/collisions as cumulative counts."""
        with self._lock:
            t = self._table
            return {
                "shards": [{
                    "shard": "0",
                    "occupied": len(t.info),
                    "capacity": t.capacity,
                    "evictions": t.evictions,
                    "collisions": t.collisions,
                }],
            }

    def drain_hot_slots(self, k: int = 64) -> List[dict]:
        """Heavy-hitter drain (ISSUE 8 tenant usage observatory):
        read-and-reset the per-slot hit accumulator and attribute the K
        hottest slots through the slot table — namespace, limit, key
        values, hit count, plus a value/max_value utilization sample and
        ttl read at drain time. One donated top-k kernel + one
        ``read_slots`` gather, entirely OFF the check path (the
        accumulator itself rides the existing check/update scatters —
        zero extra launches there, perf-smoke enforced). Attribution is
        resolved at drain: a slot recycled within one drain interval
        attributes its counts to the current occupant (or drops them
        when the slot is free) — bounded by the drain period, and only
        under table eviction pressure."""
        with self._lock:
            hits = self._state.hits
            if hits is None or k <= 0:
                return []
            now_ms = self._now_ms()
            new_hits, counts, slots = K.drain_top_hits(
                hits, min(int(k), self._capacity)
            )
            self._state = K.CounterTableState(
                self._state.values, self._state.expiry_ms, new_hits
            )
            counts = np.asarray(counts)
            slots = np.asarray(slots)
            live = counts > 0
            if not live.any():
                return []
            # Gather all k slots, filler included, and filter on the
            # host: the gather's shape is part of its program, and one
            # sized by the live count compiled anew (under this lock)
            # for every count it had not seen.
            values, ttls = K.read_slots(
                self._state, slots.astype(np.int32), np.int32(now_ms)
            )
            values = np.asarray(values)[live]
            ttls = np.asarray(ttls)[live]
            slots = slots[live]
            counts = counts[live]
            out: List[dict] = []
            info = self._table.info
            for i, slot in enumerate(slots.tolist()):
                record = {"slot": int(slot), "count": int(counts[i])}
                entry = info.get(slot)
                if entry is not None:
                    record.update(hot_attribution(
                        entry[1], int(values[i]), int(ttls[i])
                    ))
                out.append(record)
            return out

    def attribute_slots(self, slot_counts: Dict[int, int]) -> List[dict]:
        """Attribution records for externally-counted slot traffic —
        the native lane's leased admissions never reach the device
        accumulator, so the usage observatory counts them C-side and
        resolves them here: same record shape as ``drain_hot_slots``,
        counts supplied by the caller. Slots whose counter has been
        released since the counts were taken are dropped (their debit
        died with the cell)."""
        if not slot_counts:
            return []
        with self._lock:
            now_ms = self._now_ms()
            info = self._table.info
            items = [
                (slot, count) for slot, count in slot_counts.items()
                if slot in info
            ]
            if not items:
                return []
            slots = np.asarray([s for s, _ in items], np.int32)
            values, ttls = K.read_slots(
                self._state, slots, np.int32(now_ms)
            )
            values = np.asarray(values)
            ttls = np.asarray(ttls)
            out: List[dict] = []
            for i, (slot, count) in enumerate(items):
                record = {
                    "slot": int(slot), "count": int(count),
                    "source": "lease",
                }
                record.update(hot_attribution(
                    info[slot][1], int(values[i]), int(ttls[i])
                ))
                out.append(record)
            return out

    # -- the shared batched check path -------------------------------------

    def _kernel_check(self, slots, deltas, maxes, windows, req, fresh,
                      bucket, now_ms):
        """Kernel dispatch point; the replicated subclass swaps in a kernel
        that folds remote (gossiped) counts into the admission base."""
        return K.check_and_update_batch(
            self._state, slots, deltas, maxes, windows, req, fresh, bucket,
            now_ms,
        )

    def _kernel_update(self, slots, deltas, windows, fresh, bucket, now_ms):
        """Unconditional-update dispatch point (update_counter /
        apply_deltas); the replicated subclass swaps in a kernel that
        folds the gossiped remote TAT floor into bucket advances, so
        Report-role traffic cannot briefly under-count shared buckets."""
        return K.update_batch(
            self._state, slots, deltas, windows, fresh, bucket, now_ms,
        )

    def begin_check_many(self, requests: List[_Request]) -> _CheckHandle:
        """Build hit arrays and launch the kernel WITHOUT waiting for the
        device->host transfer. Table mutations are serialized under the
        lock in call order, which is also device program order, so batch
        N+1 may begin while N's results are still in flight.

        Counters whose max_value exceeds the device cap are decided
        host-side here (exact Python ints): a failing big hit strips the
        request's device deltas before the launch, so admission stays
        all-or-nothing; passing big hits apply at finish only when the
        device also admits (projected within the batch so concurrent big
        hits never over-admit)."""
        for request in requests:
            require_nonnegative_delta(request.delta)
        # Build as Python lists (then one vectorized pad+convert): per-element
        # numpy scalar stores dominate the host loop otherwise.
        slots_l: List[int] = []
        deltas_l: List[int] = []
        maxes_l: List[int] = []
        windows_l: List[int] = []
        req_l: List[int] = []
        fresh_l: List[bool] = []
        bucket_l: List[bool] = []

        with self._lock:
            now_ms = self._now_ms()
            now = self._clock()
            self._seq += 1
            seq = self._seq
            watched = self._watched_slots
            fresh_hits_by_req: List[List[Tuple[int, Counter, int]]] = []
            big_by_req: List[list] = []
            dev_info_by_req: List[List[Tuple[int, int]]] = []
            big_projected: List[Tuple[tuple, int]] = []
            watch_touches: List[int] = []
            slot_use_count: Dict[int, int] = {}
            slot_for = self._slot_for
            for r, request in enumerate(requests):
                fresh_hits: List[Tuple[int, Counter, int]] = []
                dev_info: List[Tuple[int, int]] = []
                raw_delta = int(request.delta)
                delta = min(raw_delta, K.MAX_DELTA_CAP)
                bigs, big_failed, projected = self._eval_big_hits(
                    request.ordered, raw_delta, now
                )
                big_projected.extend(projected)
                dev_delta = 0 if big_failed else delta
                adjust = delta if big_failed else 0
                for j, c in enumerate(request.ordered):
                    if self._is_big(c):
                        continue
                    slot, is_fresh = slot_for(c, create=True)
                    win, is_bucket = self._lane_of(c)
                    slots_l.append(slot)
                    deltas_l.append(dev_delta)
                    maxes_l.append(min(c.max_value, K.MAX_VALUE_CAP))
                    windows_l.append(win)
                    req_l.append(r)
                    fresh_l.append(is_fresh)
                    bucket_l.append(is_bucket)
                    slot_use_count[slot] = slot_use_count.get(slot, 0) + 1
                    dev_info.append((j, adjust))
                    if is_fresh:
                        fresh_hits.append((j, c, slot))
                        watch_touches.append(slot)
                        watched[slot] = seq
                    elif slot in watched:
                        # A later batch re-used a slot an earlier in-flight
                        # batch may want to release: the re-use wins.
                        watched[slot] = seq
                        watch_touches.append(slot)
                fresh_hits_by_req.append(fresh_hits)
                big_by_req.append(bigs)
                dev_info_by_req.append(dev_info)

            nhits = len(slots_l)
            H = _bucket(max(nhits, len(requests), 1))
            # One C-level conversion per column into a right-sized array
            # (no Python-level pad-list concatenation per batch).
            slots = _staged(slots_l, H, self._scratch, np.int32)
            deltas = _staged(deltas_l, H, 0, np.int32)
            maxes = _staged(maxes_l, H, int(_INT32_MAX), np.int32)
            windows = _staged(windows_l, H, 0, np.int32)
            req = _staged(req_l, H, H - 1, np.int32)
            fresh = _staged(fresh_l, H, False, bool)
            bucket = _staged(bucket_l, H, False, bool)

            self._state, result = self._kernel_check(
                slots, deltas, maxes, windows, req, fresh, bucket,
                np.int32(now_ms),
            )
        return _CheckHandle(
            requests, fresh_hits_by_req, slot_use_count, result, seq,
            watch_touches, big_by_req, dev_info_by_req, now, big_projected,
        )

    def finish_check_many(self, handle: _CheckHandle) -> List[Authorization]:
        """Transfer and decode one in-flight batch: load_counters side
        effects, first-limited naming, and the reference's non-load
        early-return semantics (a limited non-load request does not create
        qualified counters past its first limited hit, in_memory.rs:110-133
        — only safe to undo when no other request in the batch shares the
        freshly-allocated slot and no later batch has re-used it)."""
        import jax

        result = handle.result
        try:
            # One transfer for all three outputs (matters over remote links).
            hit_ok, remaining, ttl_ms = jax.device_get(
                (result.hit_ok, result.remaining, result.ttl_ms)
            )
        except BaseException:
            # The projection reservations must not leak when the transfer
            # fails, else those big counters under-admit forever — and
            # neither may the watch entries: a stale seq would suppress
            # every later batch's release of these slots.
            with self._lock:
                self._unproject_big(handle.big_projected)
                watched = self._watched_slots
                for slot in handle.watch_touches:
                    if watched.get(slot) == handle.seq:
                        del watched[slot]
            raise

        auths: List[Authorization] = []
        releases: List[Tuple[Counter, int]] = []
        big_applies: List[Tuple[tuple, int, int]] = []  # key, delta, window
        base = 0
        for r, request in enumerate(handle.requests):
            dev_info = handle.dev_info_by_req[r]
            bigs = handle.big_by_req[r]
            n_dev = len(dev_info)
            oks_by_j: Dict[int, bool] = {}
            for i, (j, _adjust) in enumerate(dev_info):
                oks_by_j[j] = bool(hit_ok[base + i])
            for j, ok, _rem, _ttl, _key, _c, _delta in bigs:
                oks_by_j[j] = ok
            all_ok = all(oks_by_j.values())
            if request.load:
                for i, (j, adjust) in enumerate(dev_info):
                    c = request.ordered[j]
                    c.remaining = max(int(remaining[base + i]) - adjust, 0)
                    c.expires_in = float(ttl_ms[base + i]) / 1000.0
                for j, _ok, rem, ttl, _key, _c, _delta in bigs:
                    c = request.ordered[j]
                    c.remaining = rem
                    c.expires_in = ttl
            if all_ok:
                auths.append(Authorization.OK)
                for _j, _ok, _rem, _ttl, key, c, delta in bigs:
                    big_applies.append((key, delta, c.window_seconds))
            else:
                first = min(j for j, ok in oks_by_j.items() if not ok)
                auths.append(
                    Authorization.limited_by(
                        request.ordered[first].limit.name
                    )
                )
                if not request.load:
                    for j, c, slot in handle.fresh_hits_by_req[r]:
                        if j > first and handle.slot_use_count.get(slot) == 1:
                            releases.append((c, slot))
            base += n_dev
        with self._lock:
            self._unproject_big(handle.big_projected)
            self._apply_big(big_applies, handle.now)
            watched = self._watched_slots
            for c, slot in releases:
                if watched.get(slot) != handle.seq:
                    continue
                # The table must still map this key to this slot — an
                # intervening delete/evict/clear means the slot was already
                # freed (releasing again would double-free it).
                key = self._key_of(c)
                qualified = c.is_qualified()
                mapped = (
                    self._table.qualified.get(key) == slot
                    if qualified
                    else self._table.simple.get(key) == slot
                )
                if mapped:
                    self._table.release(slot, key, qualified)
            for slot in handle.watch_touches:
                if watched.get(slot) == handle.seq:
                    del watched[slot]
        return auths

    def check_many(self, requests: List[_Request]) -> List[Authorization]:
        """Run a batch of check-all-then-update-all requests in one kernel
        launch, in list order (== serial order for exactness)."""
        return self.finish_check_many(self.begin_check_many(requests))

    # -- CounterStorage ----------------------------------------------------

    def is_within_limits(self, counter: Counter, delta: int) -> bool:
        with self._lock:
            now_ms = self._now_ms()
            if self._is_big(counter):
                key = self._key_of(counter)
                entry = self._big.get(key)
                value = (
                    entry[0].value_at(self._clock())
                    if entry is not None else 0
                ) + self._big_remote_sum(key, self._clock())
                return value + delta <= counter.max_value
            slot, _ = self._slot_for(counter, create=False)
            if slot is None:
                value = 0
            else:
                v, ttl = K.read_slots(
                    self._state, np.asarray([slot], np.int32), np.int32(now_ms)
                )
                if counter.limit.policy == "token_bucket":
                    # Bucket cells: the ttl lane is base_rel = max(TAT-now,
                    # 0); spent tokens derive from it (values lane is
                    # unspecified for buckets).
                    value = spent_tokens(
                        counter.max_value, counter.window_seconds, int(ttl[0])
                    )
                else:
                    value = int(v[0])
        return value + delta <= counter.max_value

    def add_counter(self, limit: Limit) -> None:
        if not limit.variables:
            with self._lock:
                counter = Counter(limit, {})
                if self._is_big(counter):
                    self._big_cell(counter, self._key_of(counter))
                else:
                    slot, fresh = self._slot_for(counter, create=True)
                    if fresh:
                        # No kernel batch follows this allocation, so the
                        # kernel's fresh-flag override can't clean a
                        # recycled slot — clear the cell now or the next
                        # (non-fresh) read/batch sees the old occupant.
                        self._state = K.clear_slots(
                            self._state, np.asarray([slot], np.int32)
                        )

    def update_counter(self, counter: Counter, delta: int) -> None:
        require_nonnegative_delta(delta)
        with self._lock:
            now_ms = self._now_ms()
            if self._is_big(counter):
                key = self._key_of(counter)
                cell = self._big_cell(counter, key)
                cell.update(int(delta), counter.window_seconds, self._clock())
                self._on_big_write(key)
                return
            slot, is_fresh = self._slot_for(counter, create=True)
            H = _bucket(1)
            slots = np.full(H, self._scratch, np.int32)
            deltas = np.zeros(H, np.int32)
            windows = np.zeros(H, np.int32)
            fresh = np.zeros(H, bool)
            bucket = np.zeros(H, bool)
            win, is_bucket = self._lane_of(counter)
            slots[0] = slot
            deltas[0] = min(int(delta), K.MAX_DELTA_CAP)
            windows[0] = win
            fresh[0] = is_fresh
            bucket[0] = is_bucket
            self._state = self._kernel_update(
                slots, deltas, windows, fresh, bucket, np.int32(now_ms)
            )

    def check_and_update(
        self, counters: List[Counter], delta: int, load_counters: bool
    ) -> Authorization:
        if not counters:
            return Authorization.OK
        return self.check_many([_Request(counters, delta, load_counters)])[0]

    # -- columnar entry point (native serving path) ------------------------

    def check_columnar(
        self,
        slots: np.ndarray,
        deltas: np.ndarray,
        maxes: np.ndarray,
        windows_ms: np.ndarray,
        req_ids: np.ndarray,
        fresh: np.ndarray,
        bucket: Optional[np.ndarray] = None,
    ):
        """Run one kernel over pre-built, request-ordered hit arrays (no
        per-hit Python objects). Caller pads to a bucket (use
        ``pad_hits``); returns host arrays (admitted, hit_ok, remaining,
        ttl_ms)."""
        return self.finish_check_columnar(
            self.begin_check_columnar(
                slots, deltas, maxes, windows_ms, req_ids, fresh, bucket
            )
        )

    def begin_check_columnar(
        self,
        slots: np.ndarray,
        deltas: np.ndarray,
        maxes: np.ndarray,
        windows_ms: np.ndarray,
        req_ids: np.ndarray,
        fresh: np.ndarray,
        bucket: Optional[np.ndarray] = None,
    ):
        """Launch the columnar kernel and return the in-flight device
        result (JAX async dispatch: this does not block on the device).
        ``finish_check_columnar`` collects it. Launches are ordered by
        the storage lock; the state array threads through launches, so a
        later begin is correct even while earlier results are still in
        flight — this is what lets a caller overlap batch N's device
        round trip with batch N+1's host work.

        ``bucket`` marks GCRA hits (``windows_ms`` then carries the
        emission interval); None means all fixed-window."""
        if bucket is None:
            bucket = np.zeros(slots.shape, bool)
        with self._lock:
            now_ms = self._now_ms()
            self._state, result = K.check_and_update_batch(
                self._state, slots, deltas, maxes, windows_ms, req_ids,
                fresh, bucket, np.int32(now_ms),
            )
            return result

    def finish_check_columnar(self, result, with_remaining: bool = True):
        """Block on a begin_check_columnar launch; returns host arrays
        (admitted, hit_ok, remaining, ttl_ms). ``with_remaining=False``
        transfers only the decision arrays (remaining/ttl come back as
        None) — on a high-RTT link the device->host copy is the round
        trip, so callers that don't load counters halve it."""
        import jax

        if not with_remaining:
            admitted, hit_ok = jax.device_get(
                (result.admitted, result.hit_ok)
            )
            return admitted, hit_ok, None, None
        return jax.device_get(
            (result.admitted, result.hit_ok, result.remaining,
             result.ttl_ms)
        )

    def credit_columnar(
        self,
        slots: np.ndarray,
        credits: np.ndarray,
        windows_ms: np.ndarray,
        bucket: np.ndarray,
    ) -> None:
        """Return unused leased quota to the device table (the lease
        broker's credit lane, lease/broker.py): one scatter kernel,
        floored so a credit can never create more headroom than a fresh
        cell. ``slots`` must be unique (callers aggregate per slot) and
        LIVE — the caller verifies slot->counter identity under this
        same lock, because a recycled slot's credit would land on a
        different counter. Rows are padded to the kernel's pow2 buckets
        with inert scratch writes (no per-length XLA program churn)."""
        n = int(slots.shape[0])
        if n == 0:
            return
        H = _bucket(n)
        with self._lock:
            now_ms = self._now_ms()
            self._state = K.credit_batch(
                self._state,
                _staged(slots, H, self._scratch, np.int32),
                _staged(credits, H, 0, np.int32),
                _staged(windows_ms, H, 0, np.int32),
                _staged(bucket, H, False, bool),
                np.int32(now_ms),
            )

    def pad_hits(self, arrays: Tuple[np.ndarray, ...], nhits: int):
        """Pad (slots, deltas, maxes, windows, req_ids, fresh[, bucket])
        to the next bucket with inert scratch hits."""
        H = _bucket(max(nhits, 1))
        slots, deltas, maxes, windows, req, fresh = arrays[:6]
        padded = (
            _staged(slots, H, self._scratch, np.int32),
            _staged(deltas, H, 0, np.int32),
            _staged(maxes, H, int(_INT32_MAX), np.int32),
            _staged(windows, H, 0, np.int32),
            _staged(req, H, H - 1, np.int32),
            _staged(fresh, H, False, bool),
        )
        if len(arrays) > 6:
            padded += (_staged(arrays[6], H, False, bool),)
        return padded

    # -- tier migration primitives (tier/storage.py) -----------------------

    def peek_slots(self, slots) -> Tuple[np.ndarray, np.ndarray]:
        """(value, ttl_ms) host arrays for ``slots`` at the current
        clock — the read half of an exact demotion. Caller holds the
        lock: the read must be atomic with the residency change it
        feeds. Padded to the kernel's pow2 buckets so migration-batch
        peeks of any size reuse a handful of compiled read programs."""
        n = len(slots)
        H = _bucket(n)
        now_ms = self._now_ms()
        values, ttls = K.read_slots(
            self._state,
            _staged(np.asarray(slots, np.int32), H, self._scratch, np.int32),
            np.int32(now_ms),
        )
        return np.asarray(values)[:n], np.asarray(ttls)[:n]

    def seed_slot_values(self, slots, values, expiry_rel_ms) -> None:
        """Absolute cell write for ``slots`` (tier promotion): value and
        epoch-relative expiry land verbatim (ops/kernel.py seed_slots),
        preserving the counter's exact remaining window — the update
        lane's ``fresh`` flag would restart it. Caller holds the lock;
        rows are padded to the pow2 bucket with inert scratch writes."""
        n = len(slots)
        if n == 0:
            return
        H = _bucket(n)
        self._state = K.seed_slots(
            self._state,
            _staged(np.asarray(slots, np.int32), H, self._scratch, np.int32),
            _staged(np.asarray(values, np.int32), H, 0, np.int32),
            _staged(np.asarray(expiry_rel_ms, np.int32), H, 0, np.int32),
        )

    def get_counters(self, limits: Set[Limit]) -> Set[Counter]:
        out: Set[Counter] = set()
        with self._lock:
            now_ms = self._now_ms()
            now = self._clock()
            namespaces = {limit.namespace for limit in limits}
            # Gather ONLY the matching live slots — O(matching counters)
            # transferred, not O(capacity) (the reference iterates a
            # namespace prefix the same way, rocksdb_storage.rs:91-130).
            matching: List[Tuple[int, Counter]] = [
                (slot, counter)
                for slot, (_key, counter) in self._table.info.items()
                if counter.limit in limits or counter.namespace in namespaces
            ]
            if matching:
                slot_arr = np.asarray([s for s, _c in matching], np.int32)
                values, ttls = K.read_slots(
                    self._state, slot_arr, np.int32(now_ms)
                )
                values = np.asarray(values)
                ttls = np.asarray(ttls)
                for i, (_slot, counter) in enumerate(matching):
                    ttl_ms = int(ttls[i])
                    if ttl_ms <= 0:
                        # fixed window expired / bucket full: no live state
                        continue
                    c = counter.key()
                    if c.limit.policy == "token_bucket":
                        c.remaining = c.max_value - spent_tokens(
                            c.max_value, c.window_seconds, ttl_ms
                        )
                    else:
                        c.remaining = c.max_value - int(values[i])
                    c.expires_in = ttl_ms / 1000.0
                    out.add(c)
            self._emit_big_counters(limits, namespaces, now, out)
        return out

    def delete_counters(self, limits: Set[Limit]) -> None:
        with self._lock:
            doomed: List[int] = []
            for slot, (key, counter) in list(self._table.info.items()):
                if counter.limit in limits:
                    doomed.append(slot)
                    self._table.release(slot, key, counter.is_qualified())
            if doomed:
                self._state = K.clear_slots(
                    self._state, np.asarray(doomed, np.int32)
                )
            self._delete_big(limits)

    def _replace_table(self) -> "_SlotTable":
        """Swap in a fresh slot table, carrying the coherence hooks over
        and firing the wholesale invalidation (every previously-issued
        slot index is dead). Caller holds the lock."""
        old = self._table
        self._table = _SlotTable(self._capacity)
        self._table.on_native_release = old.on_native_release
        self._table.on_slot_release = old.on_slot_release
        self._table.on_clear = old.on_clear
        if old.on_clear is not None:
            old.on_clear()
        return self._table

    def clear(self) -> None:
        with self._lock:
            self._replace_table()
            self._state = K.make_table(self._capacity)
            self._watched_slots.clear()
            self._clear_big()

    def apply_deltas(self, items):
        """Authority-side batch apply for write-behind caches: one
        update_batch + one read, vectorized (the device table playing the
        shared-Redis role of the reference's cached topology)."""
        for _counter, delta in items:
            require_nonnegative_delta(delta)
        with self._lock:
            now_ms = self._now_ms()
            now = self._clock()
            dev_items: List[Tuple[int, Counter, int]] = []
            results: List[Optional[Tuple[int, float]]] = [None] * len(items)
            for i, (counter, delta) in enumerate(items):
                if self._is_big(counter):
                    key = self._key_of(counter)
                    cell = self._big_cell(counter, key)
                    value = cell.update(
                        int(delta), counter.window_seconds, now
                    )
                    self._on_big_write(key)
                    results[i] = (value, cell.ttl(now))
                else:
                    dev_items.append((i, counter, delta))
            if dev_items:
                n = len(dev_items)
                H = _bucket(n)
                slots = np.full(H, self._scratch, np.int32)
                deltas = np.zeros(H, np.int32)
                windows = np.zeros(H, np.int32)
                fresh = np.zeros(H, bool)
                bucket = np.zeros(H, bool)
                for k, (_i, counter, delta) in enumerate(dev_items):
                    slot, is_fresh = self._slot_for(counter, create=True)
                    win, is_bucket = self._lane_of(counter)
                    slots[k] = slot
                    deltas[k] = min(int(delta), K.MAX_DELTA_CAP)
                    windows[k] = win
                    fresh[k] = is_fresh
                    bucket[k] = is_bucket
                self._state = self._kernel_update(
                    slots, deltas, windows, fresh, bucket, np.int32(now_ms)
                )
                values, ttls = K.read_slots(
                    self._state, slots[:n], np.int32(now_ms)
                )
                values = np.asarray(values)
                ttls = np.asarray(ttls)
                for k, (i, counter, _delta) in enumerate(dev_items):
                    if bucket[k]:
                        value = spent_tokens(
                            counter.max_value, counter.window_seconds,
                            int(ttls[k]),
                        )
                    else:
                        value = int(values[k])
                    results[i] = (value, float(ttls[k]) / 1000.0)
        return results

    # -- checkpoint / resume (SURVEY.md §5) ---------------------------------

    def snapshot(self, path: str) -> None:
        """Persist the counter state (device cells + host key space) so a
        restart resumes counting — the reopen semantics the reference gets
        from RocksDB (rocksdb_storage.rs:237-287), for the device table.

        Sparse: only occupied slots are transferred and written, so the
        checkpoint costs O(live counters), not O(capacity)."""
        import pickle

        with self._lock:
            occupied = np.asarray(sorted(self._table.info), np.int32)
            if occupied.size:
                # Device-side gather: only the occupied cells cross the
                # host link, not the whole table.
                values = np.asarray(self._state.values[occupied])
                expiry = np.asarray(self._state.expiry_ms[occupied])
            else:
                values = np.zeros(0, np.int32)
                expiry = np.zeros(0, np.int32)
            table = {
                "capacity": self._capacity,
                "cache_size": self._cache_size,
                "epoch": self._epoch,
                **self._table.dump(),
                "big": {
                    key: (
                        (cell.tat, cell.scale, counter)
                        if isinstance(cell, GcraValue)
                        else (cell.value_raw, cell.expiry, counter)
                    )
                    for key, (cell, counter) in self._big.items()
                },
            }
        with open(path, "wb") as f:
            pickle.dump(
                {
                    "format": 2,
                    "slots": occupied,
                    "values": values,
                    "expiry": expiry,
                    "table": table,
                },
                f,
            )

    def _apply_snapshot(self, data: dict) -> None:
        """Load checkpoint contents into THIS storage (caller holds no
        lock; capacities already verified to match)."""
        table = data["table"]
        with self._lock:
            # Keep the saved epoch so absolute expiries stay correct;
            # _now_ms rebases on its own schedule afterwards.
            self._epoch = table["epoch"]
            if data.get("format", 1) >= 2:
                slots = np.asarray(data["slots"], np.int32)
                if slots.size:
                    self._state = K.CounterTableState(
                        values=self._state.values.at[slots].set(
                            K.jnp.asarray(data["values"])
                        ),
                        expiry_ms=self._state.expiry_ms.at[slots].set(
                            K.jnp.asarray(data["expiry"])
                        ),
                        # telemetry, not state: checkpoints never carry
                        # the hit accumulator — restarts count afresh
                        hits=self._state.hits,
                    )
            else:  # round-1 dense checkpoints
                self._state = K.CounterTableState(
                    values=K.jnp.asarray(data["values"]),
                    expiry_ms=K.jnp.asarray(data["expiry"]),
                    hits=self._state.hits,
                )
            self._replace_table()
            self._table.load(table, 0, self._capacity)
            seed_slots: List[int] = []
            seed_tats: List[int] = []
            for key, (value, expiry, counter) in table.get("big", {}).items():
                # Same pre-policy key migration as _SlotTable.load: old
                # checkpoints hold 4-tuple limit identities.
                key = _migrate_key(key)
                cell = restore_cell(counter.limit, value, expiry)
                if isinstance(cell, GcraValue) and not self._is_big(counter):
                    # Routing migration: pre-r4 checkpoints kept EVERY
                    # token bucket in the big host map; device-eligible
                    # buckets now live in the device table. Seed the
                    # device TAT cell from the saved state — leaving the
                    # entry in _big would orphan it (never consulted →
                    # bucket silently resets to full) while
                    # _emit_big_counters kept emitting the stale cell.
                    slot, _fresh = self._slot_for(counter, create=True)
                    seed_slots.append(slot)
                    # GcraValue.tat is absolute ms (scale 1 when device
                    # eligible); the device lane is relative to _epoch.
                    # TAT <= now means "full bucket", same as 0.
                    seed_tats.append(min(
                        max(int(cell.tat) - int(self._epoch * 1000), 0),
                        int(_INT32_MAX),
                    ))
                    continue
                self._big[key] = (cell, counter)
            if seed_slots:
                idx = np.asarray(seed_slots, np.int32)
                self._state = K.CounterTableState(
                    values=self._state.values.at[idx].set(0),
                    expiry_ms=self._state.expiry_ms.at[idx].set(
                        np.asarray(seed_tats, np.int32)
                    ),
                    hits=self._state.hits,
                )

    def load_snapshot(self, path: str) -> None:
        """Restore a checkpoint into an already-constructed storage (the
        replicated subclass restores this way: its constructor owns the
        broker wiring, then state loads in)."""
        import pickle

        with open(path, "rb") as f:
            data = pickle.load(f)
        capacity = data["table"]["capacity"]
        if capacity != self._capacity:
            raise StorageError(
                f"snapshot capacity {capacity} != storage capacity "
                f"{self._capacity} (slot indices would shift)"
            )
        self._apply_snapshot(data)

    @classmethod
    def restore(
        cls, path: str, cache_size=None, clock=time.time
    ) -> "TpuStorage":
        """``cache_size`` may be overridden; capacity is fixed by the
        checkpoint (slot indices would shift otherwise)."""
        import pickle

        with open(path, "rb") as f:
            data = pickle.load(f)
        table = data["table"]
        self = cls(
            capacity=table["capacity"],
            cache_size=cache_size or table["cache_size"],
            clock=clock,
        )
        self._apply_snapshot(data)
        return self

    def close(self) -> None:
        pass
