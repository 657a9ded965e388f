"""TpuShardedStorage — the multi-chip counter backend.

Serves the `CounterStorage` protocol over the sharded mesh kernel
(parallel/mesh.py): the counter table is split over the mesh's "shard"
axis, the host routes every counter to its owner shard by key hash (the
ICI analogue of Redis-cluster hash-tag sharding,
/root/reference/limitador/src/storage/keys.rs:1-13), and each
``check_many`` batch is ONE ``shard_map`` launch:

- per-shard hit arrays `[n_shards, H]`, requests coupled across shards by
  ``pmin`` over the replicated request vector (a request spanning shards
  is admitted all-or-nothing — exactness preserved);
- namespaces named in ``global_namespaces`` live in the psum global
  region: one slot index shared by every shard, each shard holding a
  per-device partial, the admission base read as ``psum`` of live
  partials (the CRDT read-as-sum of cr_counter_value.rs:38-46 riding
  ICI). Over-admission for those is bounded by one in-flight batch per
  remote shard — the same contract the reference documents for its
  distributed mode (redis_cached.rs:25-41).

The existing MicroBatcher serves this class unchanged (it only needs
``check_many``), so the gRPC/HTTP planes can run multi-chip by swapping
the storage (BASELINE.json config 5, doc/topologies.md:1-37).

Scaling discipline (ISSUE 4)
----------------------------
Three rules keep throughput scaling with device count instead of against
it (the always-coupled path ran slower on eight shards than on one):

- **Collective-lean launches**: staging classifies each batch — psum
  only when a global-namespace hit is present, pmin only when some
  request actually spans shards (``coupled``); the common owner-sharded
  batch runs with shard-local request ids and ZERO collectives
  (parallel/mesh.py "Collective-lean variants"). Launch counts per
  variant are exported as the ``sharded_launches`` metric family.
- **Genuinely sharded staging**: hits are bucketed per shard on the host
  (memoized ``_stable_hash`` routing + the vectorized partition of
  storage.py ``_partition_positions``/``_scatter_rows``) and
  ``device_put`` with the mesh sharding, so each shard uploads only its
  own rows — never a replicated [n, H] batch.
- **In-place tables**: every table-mutating kernel donates the counter
  buffers (``sharded_check_and_update``/``sharded_update``/
  ``sharded_clear_cells``), so XLA updates the [n_shards, L+1] table in
  place instead of copying it per batch; host-side slot zeroing rides
  the donated clear kernel, not a full-table ``.at[].set`` copy.

``begin_check_many``/``finish_check_many`` split the launch from the
device->host transfer exactly like TpuStorage, so the MicroBatcher
pipelines sharded batches (and chunked dispatch overlaps sub-batches)
the same way it does single-chip ones.
"""

from __future__ import annotations

import threading
import time
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
from ..storage.gcra import GcraValue, restore_cell, spent_tokens
from ..ops import kernel as K
from ..routing import RouteMemo, counter_key, stable_hash
from ..parallel.mesh import (
    ShardedCounterState,
    batch_sharding,
    make_mesh,
    make_sharded_table,
    sharded_check_and_update,
    sharded_clear_cells,
    sharded_drain_top_hits,
    sharded_update,
)
from .storage import (
    _BigLimitMixin,
    _bucket,
    _migrate_key,
    _partition_positions,
    _Request,
    _scatter_rows,
    _SlotTable,
    hot_attribution,
)

__all__ = [
    "TpuShardedStorage",
    "METRIC_FAMILIES",
    "snapshot_manifest",
    "snapshot_items",
]

#: metric families this subsystem owns (cross-checked against
#: observability/metrics.py by tools/lint.py's registry lint): per-variant
#: multi-chip launch counts + the bounded key->owner-shard memo's
#: hit/miss/eviction/size telemetry, polled off ``launch_stats()`` at
#: render time.
METRIC_FAMILIES = (
    "sharded_launches",
    "sharded_route_memo_hits",
    "sharded_route_memo_misses",
    "sharded_route_memo_evictions",
    "sharded_route_memo_size",
)

#: sharded_launches label values: lean = no collective at all, coupled =
#: pmin request coupling only, global = psum global region present.
LAUNCH_VARIANTS = ("lean", "coupled", "global")

_INT32_MAX = int(np.iinfo(np.int32).max)


# Ownership hash, shared with the ingress-tier routers (routing.py) so
# every layer agrees about who owns a key. Kept under the historical
# name — snapshots re-route keys through it on restore.
_stable_hash = stable_hash


class _ShardedHandle:
    """In-flight sharded batch: kernel launched, device->host transfer
    pending. Produced by ``begin_check_many``, consumed by
    ``finish_check_many`` — the sharded analogue of storage.py's
    ``_CheckHandle``, carrying the flat staging columns so decode is a
    vectorized gather instead of per-hit Python."""

    __slots__ = (
        "requests", "result", "coupled", "seq", "now", "shard_ids", "pos",
        "slot_col", "glob_col", "j_l", "starts", "adjust_by_req", "home",
        "local_ids", "fresh_by_req", "big_by_req", "big_projected",
        "watch_touches",
    )

    def __init__(self, requests, result, coupled, seq, now, shard_ids, pos,
                 slot_col, glob_col, j_l, starts, adjust_by_req, home,
                 local_ids, fresh_by_req, big_by_req, big_projected,
                 watch_touches):
        self.requests = requests
        self.result = result
        self.coupled = coupled
        self.seq = seq
        self.now = now
        self.shard_ids = shard_ids
        self.pos = pos
        self.slot_col = slot_col
        self.glob_col = glob_col
        self.j_l = j_l
        self.starts = starts
        self.adjust_by_req = adjust_by_req
        self.home = home            # lean mode: owner shard per request
        self.local_ids = local_ids  # lean mode: shard-local request id
        self.fresh_by_req = fresh_by_req
        self.big_by_req = big_by_req
        self.big_projected = big_projected
        self.watch_touches = watch_touches


class TpuShardedStorage(_BigLimitMixin, CounterStorage):
    supports_token_bucket = True  # device bucket lane / exact host path

    def _is_big(self, counter: Counter) -> bool:
        # A TAT cell cannot be a psum global partial: token buckets in
        # global namespaces stay on the node-local exact host path.
        # Owner-sharded buckets ride the device lane like any counter.
        if (
            counter.limit.policy == "token_bucket"
            and counter.namespace in self._global_ns
        ):
            return True
        return _BigLimitMixin._is_big(self, counter)

    def __init__(
        self,
        mesh=None,
        local_capacity: int = 1 << 17,
        cache_size: Optional[int] = None,
        global_namespaces: Sequence[str] = (),
        global_region: int = 1024,
        clock=time.time,
    ):
        """``local_capacity`` sizes each shard's table (8 bytes/counter of
        HBM per shard); slots below ``global_region`` are reserved for
        psum-replicated global counters. ``cache_size`` caps qualified
        counters across the whole mesh."""
        self._mesh = mesh if mesh is not None else make_mesh()
        self._n = self._mesh.shape["shard"]
        if global_region >= local_capacity:
            raise ValueError("global_region must be < local_capacity")
        self._lock = threading.RLock()
        self._clock = clock
        self._local_capacity = int(local_capacity)
        self._global_region = int(global_region)
        self._global_ns = set(global_namespaces)
        total_local = self._n * (local_capacity - global_region)
        self._cache_size = int(cache_size) if cache_size else total_local
        self._per_shard_cache = max(self._cache_size // self._n, 1)
        self._scratch = self._local_capacity  # padding slot (row L)
        self._tables: List[_SlotTable] = []
        self._gtable = _SlotTable(self._global_region)
        self._rr = 0  # round-robin shard for global-counter deltas
        # Memoized key -> owner shard (the crc32 hash is pure; recomputing
        # repr+crc per hit was the staging pass's hot spot). LRU-bounded
        # (routing.RouteMemo): the old dict grew one entry per unique key
        # — unbounded at the 1M+ key regime this storage exists for.
        self._shard_memo = RouteMemo(4 * self._cache_size)
        # Batch input sharding: device_put hit columns with this so each
        # shard uploads only its own rows.
        self._sharding = batch_sharding(self._mesh)
        # Pipelining bookkeeping (the TpuStorage discipline): batch seq +
        # last-touch seq of watched slots, keyed (shard, slot) for locals
        # and (-1, slot) for the psum global region.
        self._seq = 0
        self._watched: Dict[Tuple[int, int], int] = {}
        # Per-variant launch tallies (the sharded_launches families).
        self._launches: Dict[str, int] = dict.fromkeys(LAUNCH_VARIANTS, 0)
        # Host-side fallback for max_value > device cap (_BigLimitMixin).
        self._init_big(self._cache_size)
        self._reset_tables()
        self._state = make_sharded_table(self._mesh, self._local_capacity)
        self._epoch = clock()
        #: pod-mode snapshot manifest (ISSUE 15): the server sets
        #: ``{"owned_shards": [lo, hi), "topology": {...}}`` so every
        #: checkpoint records WHICH global shard block this host owned
        #: when it was taken — the key a post-membership-change restore
        #: re-maps slices by (``snapshot_manifest``/``snapshot_items``).
        self.snapshot_meta: Optional[dict] = None

    def _reset_tables(self) -> None:
        self._tables = []
        for _ in range(self._n):
            t = _SlotTable(self._local_capacity)
            # Shard-local slots live in [global_region, local_capacity).
            t.free = list(
                range(self._local_capacity - 1, self._global_region - 1, -1)
            )
            self._tables.append(t)
        self._gtable = _SlotTable(self._global_region)

    # -- time ---------------------------------------------------------------

    def _now_ms(self) -> int:
        now = int((self._clock() - self._epoch) * 1000)
        if now > (1 << 30):
            shift = now - 1000
            self._state = ShardedCounterState(
                self._state.values,
                K.rebase_epoch_chunked(self._state.expiry_ms, shift),
                self._state.hits,
            )
            self._epoch += shift / 1000.0
            now -= shift
        return now

    # -- slot routing -------------------------------------------------------

    # Routed identity, shared with the ingress-tier routers
    # (routing.counter_key): both layers must hash the same bytes.
    _key_of = staticmethod(counter_key)

    def _is_global(self, counter: Counter) -> bool:
        return counter.namespace in self._global_ns

    def _clear_rows(self, rows: np.ndarray) -> None:
        """Zero per-shard cell lists via the donated clear kernel
        (``rows`` is [n, k], scratch-padded) — in-place on device, no
        full-table copy."""
        k = _bucket(rows.shape[1])
        padded = np.full((self._n, k), self._scratch, np.int32)
        padded[:, : rows.shape[1]] = rows
        self._state = sharded_clear_cells(self._mesh, self._state, padded)

    def _zero_global_slots(self, slots: List[int]) -> None:
        """A recycled global slot must not inherit stale partials on any
        shard (the kernel's psum base reads the whole global region, not
        just table-reachable cells)."""
        idx = np.asarray(slots, np.int32)
        self._clear_rows(np.broadcast_to(idx, (self._n, idx.shape[0])))

    def _evict_local(self, table: _SlotTable) -> None:
        if not table.qualified:
            raise StorageError("TPU shard table full (no evictable slots)")
        key, slot = next(iter(table.qualified.items()))
        table.release(slot, key, qualified=True)
        table.evictions += 1

    def _evict_global(self) -> None:
        if not self._gtable.qualified:
            raise StorageError("TPU global region full (no evictable slots)")
        key, slot = next(iter(self._gtable.qualified.items()))
        self._gtable.release(slot, key, qualified=True)
        self._gtable.evictions += 1
        self._zero_global_slots([slot])

    def _slot_for(
        self, counter: Counter, create: bool
    ) -> Tuple[Optional[int], Optional[int], bool, bool]:
        """Return (shard, slot, fresh, is_global). Global counters return
        shard=None (the caller picks an application shard)."""
        key = self._key_of(counter)
        qualified = counter.is_qualified()
        if self._is_global(counter):
            slot = self._gtable.lookup(key, qualified)
            if slot is not None:
                return None, slot, False, True
            if not create:
                return None, None, False, True
            if qualified and len(self._gtable.qualified) >= self._global_region:
                self._evict_global()
            if not self._gtable.free:
                self._evict_global()
            slot = self._gtable.alloc()
            if qualified:
                self._gtable.qualified[key] = slot
            else:
                self._gtable.simple[key] = slot
            self._gtable.info[slot] = (key, counter.key())
            return None, slot, True, True
        shard = self._shard_memo.get(key)
        if shard is None:
            shard = _stable_hash(key) % self._n
            self._shard_memo.put(key, shard)
        table = self._tables[shard]
        slot = table.lookup(key, qualified)
        if slot is not None:
            return shard, slot, False, False
        if not create:
            return shard, None, False, False
        if qualified:
            while len(table.qualified) >= self._per_shard_cache:
                self._evict_local(table)
        if not table.free:
            self._evict_local(table)
        slot = table.alloc()
        if qualified:
            table.qualified[key] = slot
        else:
            table.simple[key] = slot
        table.info[slot] = (key, counter.key())
        return shard, slot, True, False

    def _app_shard(self) -> int:
        """Application shard for a global-counter delta (any shard works —
        the read is psum); round-robin spreads partials."""
        s = self._rr
        self._rr = (self._rr + 1) % self._n
        return s

    def launch_stats(self) -> dict:
        """Cumulative multi-chip launch counts per collective variant
        (the ``sharded_launches`` metric family, polled baseline-
        converted off library_stats at render time): a hot path that
        is mostly ``coupled``/``global`` instead of ``lean`` means the
        limits layout is forcing collectives onto every batch. Rides
        along: the route-memo's hit/miss/eviction counters (a miss-
        heavy memo means the LRU cap is thrashing under the live key
        cardinality)."""
        with self._lock:
            stats = {"sharded_launches": dict(self._launches)}
            stats.update(self._shard_memo.stats())
            return stats

    def device_stats(self) -> dict:
        """Per-shard table stats for /debug/stats and the Prometheus
        shard gauges: one entry per shard-local table (capacity = the
        shard-local slot range) plus the replicated psum global region."""
        with self._lock:
            shards = [{
                "shard": str(i),
                "occupied": len(t.info),
                "capacity": self._local_capacity - self._global_region,
                "evictions": t.evictions,
                "collisions": t.collisions,
            } for i, t in enumerate(self._tables)]
            if self._global_region:
                shards.append({
                    "shard": "global",
                    "occupied": len(self._gtable.info),
                    "capacity": self._global_region,
                    "evictions": self._gtable.evictions,
                    "collisions": self._gtable.collisions,
                })
            return {"shards": shards}

    def drain_hot_slots(self, k: int = 64) -> List[dict]:
        """Sharded heavy-hitter drain (ISSUE 8): one per-shard top-k
        kernel (no collective; 2*k ints per shard cross the link), then
        host-side attribution through the per-shard slot tables. A psum
        global counter's traffic lands in each hitting shard's
        accumulator row — those counts merge here by slot, attributed
        through the global table with the read-as-sum value. Returns the
        merged records hottest-first (at most k)."""
        with self._lock:
            hits = self._state.hits
            if hits is None or k <= 0:
                return []
            now_ms = self._now_ms()
            kk = min(int(k), self._local_capacity)
            new_hits, counts, slots = sharded_drain_top_hits(
                self._mesh, hits, kk
            )
            self._state = ShardedCounterState(
                self._state.values, self._state.expiry_ms, new_hits
            )
            counts = np.asarray(counts)
            slots = np.asarray(slots)
            if not (counts > 0).any():
                return []
            out: List[dict] = []
            g_counts: Dict[int, int] = {}
            # Gather the drained coordinates — never the table — all
            # n*k of them, filler included: a gather sized by the live
            # count is a new program (compiled under this lock) for
            # every count it has not seen.
            sh = np.repeat(np.arange(self._n, dtype=np.int32), kk)
            sl = slots.reshape(-1).astype(np.int32)
            vals = np.asarray(self._state.values[sh, sl]).reshape(
                self._n, kk)
            exps = np.asarray(self._state.expiry_ms[sh, sl]).reshape(
                self._n, kk)
            for s in range(self._n):
                for j in range(kk):
                    c = int(counts[s, j])
                    if c <= 0:
                        continue
                    slot = int(slots[s, j])
                    if slot < self._global_region:
                        g_counts[slot] = g_counts.get(slot, 0) + c
                        continue
                    record = {"slot": slot, "shard": s, "count": c}
                    entry = self._tables[s].info.get(slot)
                    if entry is not None:
                        ttl = max(int(exps[s, j]) - now_ms, 0)
                        value = int(vals[s, j]) if ttl > 0 else 0
                        record.update(
                            hot_attribution(entry[1], value, ttl)
                        )
                    out.append(record)
            if g_counts:
                # the whole (small, fixed-size) global region, for the
                # same reason
                gsl = np.asarray(sorted(g_counts), np.int32)
                region = self._global_region
                gvals = np.asarray(self._state.values[:, :region])[:, gsl]
                gexps = np.asarray(
                    self._state.expiry_ms[:, :region])[:, gsl]
                live = gexps > now_ms
                value_sum = (gvals * live).sum(axis=0)
                ttls = np.maximum(gexps.max(axis=0) - now_ms, 0)
                for i, slot in enumerate(gsl.tolist()):
                    record = {
                        "slot": int(slot), "shard": "global",
                        "count": g_counts[int(slot)],
                    }
                    entry = self._gtable.info.get(int(slot))
                    if entry is not None:
                        record.update(hot_attribution(
                            entry[1], int(value_sum[i]), int(ttls[i])
                        ))
                    out.append(record)
            out.sort(key=lambda r: -r["count"])
            return out[:kk]

    # -- the shared batched check path --------------------------------------

    def begin_check_many(self, requests: List[_Request]) -> "_ShardedHandle":
        """Stage, partition per shard, and LAUNCH one batch without
        waiting on the device (the TpuStorage begin/finish discipline, so
        the batcher overlaps batch N+1's staging with batch N's round
        trip). Table mutations serialize under the lock in call order,
        which is also device program order.

        Staging classifies the batch: ``coupled`` when any request's
        device hits span shards (pmin rides along), ``has_global`` when
        any hit lands in the psum region — otherwise the launch is the
        collective-free lean variant with shard-local request ids.
        Counters with max_value beyond the device cap are decided
        host-side here, exactly as in TpuStorage.begin_check_many."""
        import jax

        for request in requests:
            require_nonnegative_delta(request.delta)
        n = self._n
        # Flat per-hit columns (Python lists; one C-level conversion +
        # one vectorized per-shard scatter after the loop).
        shard_l: List[int] = []
        slot_l: List[int] = []
        delta_l: List[int] = []
        max_l: List[int] = []
        win_l: List[int] = []
        req_l: List[int] = []
        fresh_l: List[bool] = []
        bucket_l: List[bool] = []
        glob_l: List[bool] = []
        j_l: List[int] = []
        with self._lock:
            now_ms = self._now_ms()
            now = self._clock()
            self._seq += 1
            seq = self._seq
            watched = self._watched
            watch_touches: List[Tuple[int, int]] = []
            fresh_by_req: List[List[Tuple[int, Counter, int, int, bool]]] = []
            big_by_req: List[list] = []
            big_projected: List[Tuple[tuple, int]] = []
            starts: List[int] = []      # flat-hit range start per request
            adjust_by_req: List[int] = []
            home_l: List[int] = []      # owner shard per request (-1 none)
            coupled = False
            slot_for = self._slot_for
            lane_of = self._lane_of
            is_big = self._is_big
            for r, request in enumerate(requests):
                starts.append(len(slot_l))
                raw_delta = int(request.delta)
                delta = min(raw_delta, K.MAX_DELTA_CAP)
                bigs, big_failed, projected = self._eval_big_hits(
                    request.ordered, raw_delta, now
                )
                big_projected.extend(projected)
                dev_delta = 0 if big_failed else delta
                adjust_by_req.append(delta if big_failed else 0)
                home = -1
                fresh_hits: List[Tuple[int, Counter, int, int, bool]] = []
                for j, c in enumerate(request.ordered):
                    if is_big(c):
                        continue
                    shard, slot, is_fresh, is_g = slot_for(c, create=True)
                    if is_g:
                        shard = self._app_shard()
                    if home < 0:
                        home = shard
                    elif shard != home:
                        coupled = True
                    win, is_bucket = lane_of(c)
                    shard_l.append(shard)
                    slot_l.append(slot)
                    delta_l.append(dev_delta)
                    max_l.append(min(c.max_value, K.MAX_VALUE_CAP))
                    win_l.append(win)
                    req_l.append(r)
                    fresh_l.append(is_fresh)
                    bucket_l.append(is_bucket)
                    glob_l.append(is_g)
                    j_l.append(j)
                    wkey = (-1, slot) if is_g else (shard, slot)
                    if is_fresh:
                        fresh_hits.append((j, c, shard, slot, is_g))
                        watched[wkey] = seq
                        watch_touches.append(wkey)
                    elif wkey in watched:
                        # A later batch re-used a slot an earlier in-flight
                        # batch may want to release: the re-use wins.
                        watched[wkey] = seq
                        watch_touches.append(wkey)
                home_l.append(home)
                fresh_by_req.append(fresh_hits)
                big_by_req.append(bigs)
            starts.append(len(slot_l))

            R = len(requests)
            shard_ids = np.asarray(shard_l, np.int32)
            counts, pos = _partition_positions(shard_ids, n)
            max_count = int(counts.max(initial=0))
            if coupled:
                # n*H must cover every request id (big-only requests
                # still consume an id even with zero device hits).
                H = _bucket(max(max_count, (R + n - 1) // n, 1))
                req_col = np.asarray(req_l, np.int32)
                req_fill = n * H - 1
                home = local_ids = None
            else:
                H = _bucket(max(max_count, 1))
                # Shard-local request ids: dense per shard, assigned in
                # request order (nondecreasing within each shard's rows).
                home = np.asarray(home_l, np.int32)
                mask = home >= 0
                local_ids = np.full(R, H - 1, np.int32)
                if mask.any():
                    _lc, lpos = _partition_positions(home[mask], n)
                    local_ids[mask] = lpos.astype(np.int32)
                req_col = local_ids[np.asarray(req_l, np.intp)]
                req_fill = H - 1
            slot_col = np.asarray(slot_l, np.int32)
            glob_col = np.asarray(glob_l, bool)
            has_global = bool(glob_col.any())
            cols = _scatter_rows(shard_ids, pos, n, H, (
                (slot_col, self._scratch, np.int32),
                (delta_l, 0, np.int32),
                (max_l, _INT32_MAX, np.int32),
                (win_l, 0, np.int32),
                (req_col, req_fill, np.int32),
                (fresh_l, False, bool),
                (bucket_l, False, bool),
                (glob_col, False, bool),
            ))
            try:
                # Sharded upload: each shard receives only its own rows.
                cols = jax.device_put(tuple(cols), self._sharding)
                self._state, result = sharded_check_and_update(
                    self._mesh, self._state, *cols, np.int32(now_ms),
                    global_region=self._global_region,
                    coupled=coupled, has_global=has_global,
                )
            except BaseException:
                # Projection reservations must not leak on a failed launch.
                self._unproject_big(big_projected)
                raise
            self._launches[
                "global" if has_global
                else ("coupled" if coupled else "lean")
            ] += 1
        return _ShardedHandle(
            requests, result, coupled, seq, now, shard_ids, pos, slot_col,
            glob_col, np.asarray(j_l, np.int32), np.asarray(starts, np.intp),
            adjust_by_req, home, local_ids, fresh_by_req, big_by_req,
            big_projected, watch_touches,
        )

    def finish_check_many(
        self, handle: "_ShardedHandle"
    ) -> List[Authorization]:
        """Transfer and decode one in-flight batch: load_counters side
        effects, first-limited naming, and the non-load early-return slot
        release (guarded by the watched-slot seq so a later in-flight
        batch's re-use of the slot wins — same contract as
        TpuStorage.finish_check_many)."""
        import jax

        result = handle.result
        try:
            admitted, hit_ok, remaining, ttl_ms = jax.device_get((
                result.admitted, result.hit_ok, result.remaining,
                result.ttl_ms,
            ))
        except BaseException:
            with self._lock:
                self._unproject_big(handle.big_projected)
                # The watch entries must not outlive the batch either: a
                # stale seq would suppress every later batch's release
                # of these slots (leaking qualified slots under repeated
                # device faults).
                watched = self._watched
                for wkey in handle.watch_touches:
                    if watched.get(wkey) == handle.seq:
                        del watched[wkey]
            raise

        requests = handle.requests
        shard_ids, pos = handle.shard_ids, handle.pos
        starts = handle.starts
        j_l = handle.j_l
        R = len(requests)
        # Vectorized flat views (one fancy gather per output, not a
        # Python pair loop per hit).
        ok_flat = hit_ok[shard_ids, pos]
        rem_flat = ttl_flat = None
        if any(request.load for request in requests):
            rem_flat = remaining[shard_ids, pos]
            ttl_flat = ttl_ms[shard_ids, pos]
        if handle.coupled:
            adm_by_req = admitted[:R]
        else:
            adm_by_req = np.ones(R, bool)
            mask = handle.home >= 0
            if mask.any():
                adm_by_req[mask] = admitted[
                    handle.home[mask], handle.local_ids[mask]
                ]
        use_counts = None  # computed lazily, only when a release is due

        auths: List[Authorization] = []
        big_applies: List[Tuple[tuple, int, int]] = []
        releases: List[Tuple[Counter, int, int, bool]] = []
        for r, request in enumerate(requests):
            s0, s1 = int(starts[r]), int(starts[r + 1])
            bigs = handle.big_by_req[r]
            dev_ok = bool(adm_by_req[r]) if s1 > s0 else True
            big_ok = all(ok for _j, ok, *_rest in bigs)
            if request.load:
                adjust = handle.adjust_by_req[r]
                for i in range(s0, s1):
                    c = request.ordered[int(j_l[i])]
                    c.remaining = max(int(rem_flat[i]) - adjust, 0)
                    c.expires_in = float(ttl_flat[i]) / 1000.0
                for j, _ok, rem, ttl, _key, _c, _d in bigs:
                    c = request.ordered[j]
                    c.remaining = rem
                    c.expires_in = ttl
            if dev_ok and big_ok:
                auths.append(Authorization.OK)
                for _j, _ok, _rem, _ttl, key, c, d in bigs:
                    big_applies.append((key, d, c.window_seconds))
                continue
            oks_by_j = {
                int(j_l[i]): bool(ok_flat[i]) for i in range(s0, s1)
            }
            for j, ok, *_rest in bigs:
                oks_by_j[j] = ok
            limited_js = [j for j, ok in oks_by_j.items() if not ok]
            first = min(limited_js) if limited_js else 0
            auths.append(
                Authorization.limited_by(request.ordered[first].limit.name)
            )
            if not request.load:
                # Non-load early-return semantics (in_memory.rs:110-133):
                # drop qualified slots allocated past the first limited
                # hit, when no other hit in the batch shares them.
                for j, c, shard, slot, is_g in handle.fresh_by_req[r]:
                    if j <= first:
                        continue
                    if use_counts is None:
                        use_counts = self._slot_use_counts(
                            shard_ids, handle.slot_col, handle.glob_col
                        )
                    use = (-slot - 1) if is_g else (shard << 32) + slot
                    if use_counts.get(use) == 1:
                        releases.append((c, shard, slot, is_g))
        with self._lock:
            self._unproject_big(handle.big_projected)
            self._apply_big(big_applies, handle.now)
            watched = self._watched
            for c, shard, slot, is_g in releases:
                wkey = (-1, slot) if is_g else (shard, slot)
                if watched.get(wkey) != handle.seq:
                    continue
                # The table must still map this key to this slot — an
                # intervening delete/evict/clear means the slot was
                # already freed (releasing again would double-free it).
                key = self._key_of(c)
                qualified = c.is_qualified()
                table = self._gtable if is_g else self._tables[shard]
                mapped = (
                    table.qualified.get(key) == slot
                    if qualified else table.simple.get(key) == slot
                )
                if mapped:
                    self._release(c, shard, slot, is_g)
            for wkey in handle.watch_touches:
                if watched.get(wkey) == handle.seq:
                    del watched[wkey]
        return auths

    @staticmethod
    def _slot_use_counts(shard_ids, slot_col, glob_col) -> Dict[int, int]:
        """Batch-wide use count per device cell, as a composite-int map
        (negative = global slot). Vectorized; built only when a non-load
        limited request actually has fresh slots to consider releasing."""
        comp = np.where(
            glob_col,
            -(slot_col.astype(np.int64) + 1),
            shard_ids.astype(np.int64) * (1 << 32) + slot_col,
        )
        uniq, cnt = np.unique(comp, return_counts=True)
        return dict(zip(uniq.tolist(), cnt.tolist()))

    def check_many(self, requests: List[_Request]) -> List[Authorization]:
        """One sharded launch deciding a batch of requests in list order
        (same exactness contract as TpuStorage.check_many; cross-shard
        requests couple via pmin when present)."""
        return self.finish_check_many(self.begin_check_many(requests))

    def _release(self, counter: Counter, shard: int, slot: int, is_g: bool):
        key = self._key_of(counter)
        if is_g:
            self._gtable.release(slot, key, counter.is_qualified())
            self._zero_global_slots([slot])
        else:
            self._tables[shard].release(slot, key, counter.is_qualified())

    # -- host reads ---------------------------------------------------------

    def _read_value(
        self, shard: Optional[int], slot: int, is_g: bool, now_ms: int
    ) -> Tuple[int, int]:
        """(live value, ttl_ms) — psum of live partials for global slots."""
        if is_g:
            vals = np.asarray(self._state.values[:, slot])
            exps = np.asarray(self._state.expiry_ms[:, slot])
            live = exps > now_ms
            value = int(vals[live].sum())
            ttl = int(exps.max() - now_ms) if live.any() else 0
            return value, max(ttl, 0)
        v = int(self._state.values[shard, slot])
        e = int(self._state.expiry_ms[shard, slot])
        if e <= now_ms:
            return 0, 0
        return v, e - now_ms

    # -- CounterStorage ------------------------------------------------------

    def is_within_limits(self, counter: Counter, delta: int) -> bool:
        with self._lock:
            now_ms = self._now_ms()
            if self._is_big(counter):
                entry = self._big.get(self._key_of(counter))
                value = (
                    entry[0].value_at(self._clock())
                    if entry is not None else 0
                )
                return value + delta <= counter.max_value
            shard, slot, _f, is_g = self._slot_for(counter, create=False)
            if slot is None:
                value = 0
            else:
                value, ttl = self._read_value(shard, slot, is_g, now_ms)
                if counter.limit.policy == "token_bucket":
                    # Bucket cells: ttl is base_rel = max(TAT - now, 0);
                    # spent tokens derive from it (values lane unspecified).
                    value = spent_tokens(
                        counter.max_value, counter.window_seconds, ttl
                    )
        return value + delta <= counter.max_value

    def add_counter(self, limit: Limit) -> None:
        if not limit.variables:
            with self._lock:
                counter = Counter(limit, {})
                if self._is_big(counter):
                    self._big_cell(counter, self._key_of(counter))
                else:
                    shard, slot, fresh, is_g = self._slot_for(
                        counter, create=True
                    )
                    if fresh and not is_g:
                        # No kernel batch follows: clear a recycled local
                        # cell (global slots are zeroed at release —
                        # _zero_global_slots — so only locals can carry a
                        # stale occupant here).
                        rows = np.full((self._n, 1), self._scratch, np.int32)
                        rows[shard, 0] = slot
                        self._clear_rows(rows)

    def update_counter(self, counter: Counter, delta: int) -> None:
        self.apply_deltas([(counter, delta)])

    def check_and_update(
        self, counters: List[Counter], delta: int, load_counters: bool
    ) -> Authorization:
        if not counters:
            return Authorization.OK
        return self.check_many([_Request(counters, delta, load_counters)])[0]

    def apply_deltas(self, items):
        """Unconditional batched increments (the Report/update path and the
        write-behind authority role): one ``sharded_update`` launch — the
        same saturating scatter-add as the single-chip authority — then two
        batched gathers (one for shard-local slots, one for the global
        region) for the authoritative values."""
        for _counter, delta in items:
            require_nonnegative_delta(delta)
        import jax

        with self._lock:
            now_ms = self._now_ms()
            now = self._clock()
            # Flat staging columns (the begin_check_many discipline).
            app_l: List[int] = []
            slot_l: List[int] = []
            delta_l: List[int] = []
            win_l: List[int] = []
            fresh_l: List[bool] = []
            bucket_l: List[bool] = []
            # loc: (shard, slot, is_global, counter) or ("big", value, ttl)
            locs: List[tuple] = []
            for counter, delta in items:
                if self._is_big(counter):
                    cell = self._big_cell(counter, self._key_of(counter))
                    value = cell.update(
                        int(delta), counter.window_seconds, now
                    )
                    locs.append(("big", value, cell.ttl(now)))
                    continue
                shard, slot, is_fresh, is_g = self._slot_for(
                    counter, create=True
                )
                win, is_bucket = self._lane_of(counter)
                app_l.append(self._app_shard() if is_g else shard)
                slot_l.append(slot)
                delta_l.append(min(int(delta), K.MAX_DELTA_CAP))
                win_l.append(win)
                fresh_l.append(is_fresh)
                bucket_l.append(is_bucket)
                locs.append((shard, slot, is_g, counter))
            n = self._n
            app_ids = np.asarray(app_l, np.int32)
            counts, pos = _partition_positions(app_ids, n)
            H = _bucket(max(int(counts.max(initial=0)), 1))
            cols = _scatter_rows(app_ids, pos, n, H, (
                (slot_l, self._scratch, np.int32),
                (delta_l, 0, np.int32),
                (win_l, 0, np.int32),
                (fresh_l, False, bool),
                (bucket_l, False, bool),
            ))
            cols = jax.device_put(tuple(cols), self._sharding)
            self._state = sharded_update(
                self._mesh, self._state, *cols, np.int32(now_ms),
            )
            # Batched authoritative reads: one gather per slot family.
            dev_locs = [loc for loc in locs if loc[0] != "big"]
            lsh = np.asarray(
                [s for s, _sl, g, _c in dev_locs if not g], np.int32
            )
            lsl = np.asarray(
                [sl for _s, sl, g, _c in dev_locs if not g], np.int32
            )
            gsl = np.asarray(
                sorted({sl for _s, sl, g, _c in dev_locs if g}), np.int32
            )
            lv = le = gv = ge = None
            if lsh.size:
                lv = np.asarray(self._state.values[lsh, lsl])
                le = np.asarray(self._state.expiry_ms[lsh, lsl])
            if gsl.size:
                gv = np.asarray(self._state.values[:, gsl])
                ge = np.asarray(self._state.expiry_ms[:, gsl])
            gpos = {int(sl): i for i, sl in enumerate(gsl)}
            out = []
            li = 0
            for loc in locs:
                if loc[0] == "big":
                    _tag, value, ttl_s = loc
                    out.append((value, ttl_s))
                    continue
                shard, slot, is_g, counter = loc
                if is_g:
                    col = gpos[slot]
                    live = ge[:, col] > now_ms
                    value = int(gv[live, col].sum())
                    ttl = (
                        max(int(ge[:, col].max()) - now_ms, 0)
                        if live.any() else 0
                    )
                else:
                    ttl = max(int(le[li]) - now_ms, 0)
                    if counter.limit.policy == "token_bucket":
                        value = spent_tokens(
                            counter.max_value, counter.window_seconds, ttl
                        )
                    else:
                        value = int(lv[li]) if le[li] > now_ms else 0
                    li += 1
                out.append((value, ttl / 1000.0))
        return out

    def get_counters(self, limits: Set[Limit]) -> Set[Counter]:
        out: Set[Counter] = set()
        with self._lock:
            now_ms = self._now_ms()
            namespaces = {limit.namespace for limit in limits}
            g_matching = [
                (slot, counter)
                for slot, (_key, counter) in self._gtable.info.items()
                if counter.limit in limits or counter.namespace in namespaces
            ]
            l_matching = [
                (shard, slot, counter)
                for shard, table in enumerate(self._tables)
                for slot, (_key, counter) in table.info.items()
                if counter.limit in limits or counter.namespace in namespaces
            ]
            # Device-side gathers of only the matching cells: O(matching)
            # transferred, not the whole [n_shards, capacity] table.
            if g_matching:
                gsl = np.asarray([s for s, _c in g_matching], np.int32)
                gv = np.asarray(self._state.values[:, gsl])
                ge = np.asarray(self._state.expiry_ms[:, gsl])
                for col, (_slot, counter) in enumerate(g_matching):
                    live = ge[:, col] > now_ms
                    if not live.any():
                        continue
                    c = counter.key()
                    c.remaining = c.max_value - int(gv[live, col].sum())
                    c.expires_in = (int(ge[:, col].max()) - now_ms) / 1000.0
                    out.add(c)
            if l_matching:
                lsh = np.asarray([s for s, _sl, _c in l_matching], np.int32)
                lsl = np.asarray([sl for _s, sl, _c in l_matching], np.int32)
                lv = np.asarray(self._state.values[lsh, lsl])
                le = np.asarray(self._state.expiry_ms[lsh, lsl])
                for i, (_shard, _slot, counter) in enumerate(l_matching):
                    ttl = int(le[i]) - now_ms
                    if ttl <= 0:
                        continue
                    c = counter.key()
                    if c.limit.policy == "token_bucket":
                        c.remaining = c.max_value - spent_tokens(
                            c.max_value, c.window_seconds, ttl
                        )
                    else:
                        c.remaining = c.max_value - int(lv[i])
                    c.expires_in = ttl / 1000.0
                    out.add(c)
            self._emit_big_counters(limits, namespaces, self._clock(), out)
        return out

    def delete_counters(self, limits: Set[Limit]) -> None:
        with self._lock:
            doomed_global: List[int] = []
            for slot, (key, counter) in list(self._gtable.info.items()):
                if counter.limit in limits:
                    self._gtable.release(slot, key, counter.is_qualified())
                    doomed_global.append(slot)
            shard_idx: List[int] = []
            slot_idx: List[int] = []
            for shard, table in enumerate(self._tables):
                for slot, (key, counter) in list(table.info.items()):
                    if counter.limit in limits:
                        table.release(slot, key, counter.is_qualified())
                        shard_idx.append(shard)
                        slot_idx.append(slot)
            if doomed_global:
                self._zero_global_slots(doomed_global)
            if shard_idx:
                si = np.asarray(shard_idx, np.int32)
                li = np.asarray(slot_idx, np.int32)
                counts, pos = _partition_positions(si, self._n)
                (rows,) = _scatter_rows(
                    si, pos, self._n, max(int(counts.max(initial=0)), 1),
                    ((li, self._scratch, np.int32),),
                )
                self._clear_rows(rows)
            self._delete_big(limits)

    def clear(self) -> None:
        with self._lock:
            self._reset_tables()
            self._clear_big()
            self._watched.clear()
            self._state = make_sharded_table(
                self._mesh, self._local_capacity
            )

    # -- checkpoint / resume -------------------------------------------------

    def snapshot(self, path: str) -> None:
        """Sparse checkpoint of the sharded table: occupied shard-local
        cells + the global region's per-shard partials + the host key
        space (same reopen semantics as TpuStorage.snapshot). When the
        server set :attr:`snapshot_meta` (pod mode, ISSUE 15) the
        payload additionally carries the OWNED-SHARD-RANGE manifest —
        ``owned_shards``/``topology`` — so a restore after a membership
        change can map slices to the new topology (``snapshot_items``)
        instead of silently loading the wrong host's table."""
        import pickle

        with self._lock:
            locs = [
                (shard, slot)
                for shard, table in enumerate(self._tables)
                for slot in table.info
            ]
            gslots = np.asarray(sorted(self._gtable.info), np.int32)
            if locs:
                lsh = np.asarray([s for s, _ in locs], np.int32)
                lsl = np.asarray([sl for _, sl in locs], np.int32)
                lvalues = np.asarray(self._state.values[lsh, lsl])
                lexpiry = np.asarray(self._state.expiry_ms[lsh, lsl])
            else:
                lvalues = lexpiry = np.zeros(0, np.int32)
            if gslots.size:
                gvalues = np.asarray(self._state.values[:, gslots])
                gexpiry = np.asarray(self._state.expiry_ms[:, gslots])
            else:
                gvalues = gexpiry = np.zeros((self._n, 0), np.int32)
            payload = {
                "format": 1,
                "n_shards": self._n,
                "local_capacity": self._local_capacity,
                "global_region": self._global_region,
                "global_namespaces": sorted(self._global_ns),
                "cache_size": self._cache_size,
                "epoch": self._epoch,
                "locs": locs,
                "lvalues": lvalues,
                "lexpiry": lexpiry,
                "gslots": gslots,
                "gvalues": gvalues,
                "gexpiry": gexpiry,
                "tables": [t.dump() for t in self._tables],
                "gtable": self._gtable.dump(),
                "big": {
                    key: (
                        (cell.tat, cell.scale, counter)
                        if isinstance(cell, GcraValue)
                        else (cell.value_raw, cell.expiry, counter)
                    )
                    for key, (cell, counter) in self._big.items()
                },
            }
            if self.snapshot_meta:
                payload["manifest"] = dict(self.snapshot_meta)
        with open(path, "wb") as f:
            pickle.dump(payload, f)

    @classmethod
    def restore(
        cls, path: str, mesh=None, cache_size=None, clock=time.time
    ) -> "TpuShardedStorage":
        """``cache_size`` (unlike capacity/region/namespaces, which govern
        key routing and must match the checkpoint) may be overridden."""
        import pickle

        with open(path, "rb") as f:
            data = pickle.load(f)
        self = cls(
            mesh=mesh,
            local_capacity=data["local_capacity"],
            cache_size=cache_size or data["cache_size"],
            global_namespaces=data["global_namespaces"],
            global_region=data["global_region"],
            clock=clock,
        )
        if self._n != data["n_shards"]:
            raise StorageError(
                f"snapshot was taken on {data['n_shards']} shards, mesh "
                f"has {self._n} (key routing would change)"
            )
        self._epoch = data["epoch"]
        values, expiry = self._state.values, self._state.expiry_ms
        locs = data["locs"]
        if locs:
            lsh = np.asarray([s for s, _ in locs], np.int32)
            lsl = np.asarray([sl for _, sl in locs], np.int32)
            values = values.at[lsh, lsl].set(np.asarray(data["lvalues"]))
            expiry = expiry.at[lsh, lsl].set(np.asarray(data["lexpiry"]))
        gslots = np.asarray(data["gslots"], np.int32)
        if gslots.size:
            values = values.at[:, gslots].set(np.asarray(data["gvalues"]))
            expiry = expiry.at[:, gslots].set(np.asarray(data["gexpiry"]))
        # The hit accumulator is telemetry, not state: restores count
        # afresh from the constructor's zeros.
        self._state = ShardedCounterState(values, expiry, self._state.hits)
        for table, dump in zip(self._tables, data["tables"]):
            table.load(dump, self._global_region, self._local_capacity)
        self._gtable.load(data["gtable"], 0, self._global_region)
        seed: List[Tuple[int, int, int]] = []
        for key, (value, exp, counter) in data.get("big", {}).items():
            key = _migrate_key(key)
            cell = restore_cell(counter.limit, value, exp)
            if isinstance(cell, GcraValue) and not self._is_big(counter):
                # Routing migration, same as TpuStorage._apply_snapshot:
                # pre-r4 checkpoints kept device-eligible buckets in the
                # big map; seed the owner shard's TAT cell instead of
                # orphaning the state. Device-eligible buckets are never
                # global (_is_big forces global-ns buckets host-side),
                # so the returned shard is always concrete.
                shard, slot, _fresh, _is_global = self._slot_for(
                    counter, create=True
                )
                seed.append((shard, slot, min(
                    max(int(cell.tat) - int(self._epoch * 1000), 0),
                    _INT32_MAX,
                )))
                continue
            self._big[key] = (cell, counter)
        if seed:
            sh = np.asarray([s for s, _, _ in seed], np.int32)
            sl = np.asarray([s for _, s, _ in seed], np.int32)
            tat = np.asarray([t for _, _, t in seed], np.int32)
            self._state = ShardedCounterState(
                self._state.values.at[sh, sl].set(0),
                self._state.expiry_ms.at[sh, sl].set(tat),
                self._state.hits,
            )
        return self

    def close(self) -> None:
        pass


# -- slice-granular checkpoint decode (elastic pod, ISSUE 15) ------------------


def snapshot_manifest(path: str) -> dict:
    """The shard-ownership manifest of a sharded checkpoint, WITHOUT
    building a storage: which global shard block the writing host owned
    and under which topology. Pre-ISSUE-15 checkpoints (no manifest)
    return an empty ``manifest`` — the caller falls back to the legacy
    ``.host<id>`` interpretation."""
    import pickle

    with open(path, "rb") as f:
        data = pickle.load(f)
    return {
        "format": data.get("format"),
        "n_shards": data.get("n_shards"),
        "manifest": dict(data.get("manifest") or {}),
    }


def _decoded_value(counter, value: int, expiry_ms: int, now_rel: int,
                   ) -> int:
    """One device cell's host-visible spend at ``now_rel`` (ms since the
    checkpoint's epoch): fixed windows read the values lane gated on
    expiry; bucket cells derive spent tokens from the TAT lane (the
    values lane is unspecified for buckets — same rule as read_slots)."""
    if counter.limit.policy == "token_bucket":
        base_rel = max(int(expiry_ms) - now_rel, 0)
        return spent_tokens(
            counter.max_value, counter.limit.seconds, base_rel
        )
    if int(expiry_ms) <= now_rel:
        return 0
    return int(value)


def snapshot_items(path: str, clock=time.time):
    """Decode a sharded checkpoint into live ``(counter, spend)`` items
    host-side — the slice-granular restore lane (ISSUE 15): after a
    membership change the owned shard ranges no longer match any single
    checkpoint file, so a restarting host decodes every sibling
    checkpoint and seeds ONLY the counters it owns under the current
    topology through the storage's ``apply_deltas`` contract (fresh
    windows, exact spends — the same accuracy contract as a failover
    journal replay). Expired cells decode to nothing."""
    import pickle

    with open(path, "rb") as f:
        data = pickle.load(f)
    now = float(clock())
    now_rel = int((now - float(data["epoch"])) * 1000)
    items = []
    tables = [dict(d.get("info", {})) for d in data.get("tables", ())]
    lvalues = np.asarray(data.get("lvalues", ()))
    lexpiry = np.asarray(data.get("lexpiry", ()))
    for i, (shard, slot) in enumerate(data.get("locs", ())):
        entry = tables[shard].get(slot) if shard < len(tables) else None
        if entry is None:
            continue
        _key, counter = entry
        value = _decoded_value(
            counter, int(lvalues[i]), int(lexpiry[i]), now_rel
        )
        if value > 0:
            items.append((counter, value))
    # global region: the read-as-sum of every shard's partial
    ginfo = dict(data.get("gtable", {}).get("info", {}))
    gslots = np.asarray(data.get("gslots", ())).tolist()
    gvalues = np.asarray(data.get("gvalues", ()))
    gexpiry = np.asarray(data.get("gexpiry", ()))
    for j, slot in enumerate(gslots):
        entry = ginfo.get(int(slot))
        if entry is None:
            continue
        _key, counter = entry
        if counter.limit.policy == "token_bucket":
            continue  # _is_big keeps global-ns buckets host-side
        if gexpiry.size and int(gexpiry[:, j].max()) <= now_rel:
            continue
        value = int(gvalues[:, j].sum()) if gvalues.size else 0
        if value > 0:
            items.append((counter, value))
    # host-side big map (over-cap limits and host buckets)
    for _key, (a, b, counter) in data.get("big", {}).items():
        cell = restore_cell(counter.limit, a, b)
        value = int(cell.value_at(now))
        if value > 0:
            items.append((counter, value))
    return items
