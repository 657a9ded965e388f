"""Native columnar RLS serving path.

The fastest end-to-end route through the framework: the gRPC handler gives
this pipeline RAW serialized RateLimitRequest bytes (identity deserializer
— Python protobuf never runs on the hot path); a micro-batch of blobs then
flows

    hot-descriptor plan cache (byte-identical repeats skip everything
    below except the kernel)                     (tpu/plan_cache.py)
    -> C++ parse + intern -> token columns       (native/hostpath.cc)
    -> compiled predicate masks (numpy)          (tpu/compiler.py)
    -> composite-key slot lookup (C++ hash map)  (native slot map)
    -> ONE fused device kernel                   (ops/kernel.py)
    -> per-request OK / OVER_LIMIT blobs (prebuilt bytes)

Python objects only materialize off the fast path: slot-map misses
(allocation via the storage's key space, kept coherent with native keys so
LRU eviction invalidates both sides), requests with multiple descriptors,
namespaces with non-vectorizable limits, and header-loading modes — all of
which route to the exact per-request pipeline.

Serving model: ``submit`` is a plain function returning an awaitable
future — no per-request coroutine/task — and the pending queue is
sharded PER EVENT LOOP, so N serving loops (threads) feed the one
device lane concurrently behind the storage lock's swap discipline.
Cross-loop future resolution stays batched (one ``call_soon_threadsafe``
per loop per batch).

Semantics are the same exact check-all-then-update-all as everywhere else;
this module only changes how fast the batch is assembled.
"""

from __future__ import annotations

import asyncio
import contextvars
import ctypes
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..core.counter import Counter
from ..core.limit import Namespace
from ..observability.device_plane import current_request_id
from ..observability.metrics import PrometheusMetrics
from ..observability.tracing import device_batch_span, tracing_enabled
from ..storage.base import StorageError
from .. import native
from ..ops import kernel as K
from ..storage.gcra import device_eligible, emission_interval_ms
from .batcher import ChunkPlanner, chunk_queue_wait
from .compiler import NamespaceCompiler
from .pipeline import CompiledTpuLimiter
from .plan_cache import (
    PLAN_FOREIGN,
    PLAN_KERNEL,
    PLAN_OK,
    PLAN_UNKNOWN,
    DecisionPlan,
    DecisionPlanCache,
)
from .storage import TpuStorage

__all__ = ["NativeRlsPipeline", "METRIC_FAMILIES"]

#: metric families owned by the native hot lane (cross-checked against
#: observability/metrics.py by tools/lint.py's registry lint): rows and
#: hits decided by the zero-Python C lane vs the Python miss lane, and
#: the C-side plan-mirror health counters.
METRIC_FAMILIES = (
    "native_lane_rows",
    "native_lane_misses",
    "native_lane_staged_hits",
    "native_lane_invalidations",
    "native_lane_overflows",
    "native_lane_plans",
    # pod fast path (ISSUE 13): the C lane's own local/foreign split —
    # pod_hot_local_share in bench rows derives from these two.
    "pod_hot_local_rows",
    "pod_hot_foreign_rows",
)


class _NsPlan:
    """Per-namespace compiled plan bound to the native interner."""

    __slots__ = ("namespace", "compiler", "limits_meta")

    def __init__(self, namespace: Namespace, compiler: NamespaceCompiler, hp):
        self.namespace = namespace
        self.compiler = compiler
        # per vectorized limit: (limit_token, max, window_s, name, limit,
        # name_token). The token is interned from the limit's stable
        # identity — compile order must NOT leak into native slot keys, or
        # a limits reload that reorders limits would alias counters (plans
        # rebuild, the native slot map does not). name_token feeds the hot
        # lane's limited-call aggregation (-1 = unnamed limit).
        self.limits_meta = [
            (
                hp.intern("limit\x00" + repr(cl.limit._identity)),
                cl.limit.max_value,
                cl.limit.window_seconds,
                cl.limit.name,
                cl.limit,
                hp.intern(cl.limit.name) if cl.limit.name else -1,
            )
            for cl in compiler.limits
        ]


class _SubmitShard:
    """Per-event-loop serving state: the pending queue one loop's
    handlers append to, plus that loop's flush task and in-flight
    bookkeeping. Each serving loop (thread) owns exactly one shard; the
    device lane behind them is shared and ordered by the storage lock."""

    __slots__ = (
        "loop", "pending", "flush_task", "sem", "inflight",
        "inflight_batches", "batch_seq",
    )

    def __init__(self, loop, max_inflight: int):
        self.loop = loop
        self.pending: List[Tuple[bytes, asyncio.Future, float, object]] = []
        self.flush_task: Optional[asyncio.Task] = None
        self.sem = asyncio.Semaphore(max_inflight)
        self.inflight: set = set()
        # seq -> dispatched-but-uncollected batch (for breaker-trip
        # draining, the MicroBatcher._inflight_batches pattern).
        self.inflight_batches: Dict[int, list] = {}
        self.batch_seq = 0


class NativeRlsPipeline:
    """Owns the native context and decides batches of raw RLS blobs.

    ``submit(blob)`` returns a future resolving to the serialized
    RateLimitResponse bytes (plain function — await it from any serving
    shard's loop). ``submit_async`` is the coroutine form for callers
    that must schedule cross-thread (the native ingress slow path).
    """

    OK_BLOB: bytes
    OVER_BLOB: bytes
    UNKNOWN_BLOB: bytes
    #: decide_many marker for rows whose counter allocation failed
    #: (transient storage error; answer UNAVAILABLE)
    STORAGE_ERROR: object

    def __init__(
        self,
        limiter: CompiledTpuLimiter,
        metrics: Optional[PrometheusMetrics] = None,
        max_delay: float = 0.0005,
        max_batch: int = 8192,
        max_inflight: int = 2,
        plan_cache_size: int = 1 << 16,
        dispatch_chunk: Optional[int] = None,
        hot_lane: Optional[bool] = None,
    ):
        if not native.available():
            raise RuntimeError(
                f"native hostpath unavailable: {native.build_error()}"
            )
        from ..server.proto import rls_pb2

        self._pb = rls_pb2
        self.OK_BLOB = rls_pb2.RateLimitResponse(
            overall_code=rls_pb2.RateLimitResponse.OK
        ).SerializeToString()
        self.OVER_BLOB = rls_pb2.RateLimitResponse(
            overall_code=rls_pb2.RateLimitResponse.OVER_LIMIT
        ).SerializeToString()
        self.UNKNOWN_BLOB = rls_pb2.RateLimitResponse(
            overall_code=rls_pb2.RateLimitResponse.UNKNOWN
        ).SerializeToString()

        self.limiter = limiter
        self._tpu = limiter._tpu
        self.storage: TpuStorage = limiter._tpu.inner
        self.metrics = metrics
        if metrics is not None and metrics.custom_label_names:
            import sys as _sys

            print(
                "warning: --metric-labels values are not evaluated on the "
                "native columnar path; custom labels will be empty for "
                "requests it serves (use --pipeline compiled for per-request "
                "label values)",
                file=_sys.stderr,
            )
        self.max_delay = max_delay
        self.max_batch = max_batch
        #: concurrent dispatched-but-uncollected batches PER SHARD; 2 is
        #: enough to keep the device busy while the host parses the next
        #: batch.
        self.max_inflight = max_inflight
        # Pipelined sub-batch dispatch (batcher.py module docstring):
        # None = auto-tuned from the queue-wait signal, 0 = monolithic.
        self.chunk_planner = ChunkPlanner(dispatch_chunk)

        self.hp = native.HostPath()
        self._interner = self.hp.as_interner()
        self._tracked: Dict[str, int] = {}
        self._plans: Dict[int, Optional[_NsPlan]] = {}  # domain token -> plan
        # Hot-descriptor decision-plan cache: raw blob -> DecisionPlan.
        # Epoch-guarded (invalidate() bumps) and slot-coherent (the slot
        # table's release hook drops plans pinning a recycled slot).
        self.plan_cache: Optional[DecisionPlanCache] = (
            DecisionPlanCache(plan_cache_size) if plan_cache_size > 0
            else None
        )
        # Per-event-loop serving shards (created lazily as loops submit).
        self._shards: Dict[object, _SubmitShard] = {}
        self._shards_lock = threading.Lock()
        self._recorder = None  # memoized from the limiter on first sight
        # Dispatch serializes host phases (the C++ context and the slot
        # path are single-threaded by design); collects may overlap.
        self._dispatch_pool = ThreadPoolExecutor(
            1, thread_name_prefix="native-dispatch"
        )
        self._collect_pool = ThreadPoolExecutor(
            max(max_inflight, 2), thread_name_prefix="native-collect"
        )
        # The C++ context is single-threaded by design; overlapping flushes
        # (timer + max_batch trigger) serialize here.
        self._native_lock = threading.Lock()
        # host_cache / native_lane phase splits of the most recent begin
        # (telemetry only; written under _native_lock, read right after
        # on the same thread).
        self._last_host_cache = 0.0
        self._last_native_lane = 0.0
        #: rebuild the native context when the interner exceeds this many
        #: distinct strings (high-cardinality values must not grow RSS
        #: without bound; device counters are keyed by the Python table, so
        #: a rebuild only costs re-warming the caches).
        self.max_interned = 4 << 20
        # eviction coherence: python slot release -> native map removal,
        # and -> plan-cache invalidation (a cached plan must never pin a
        # recycled slot).
        self.storage._table.on_native_release = self.hp.slots_remove
        if self.plan_cache is not None:
            self.storage._table.on_slot_release = (
                self.plan_cache.invalidate_slot
            )
            self.storage._table.on_clear = self.plan_cache.bump_epoch
        # The zero-Python hot lane (ISSUE 5): a C-side mirror of the
        # decision-plan cache plus one-call columnar staging + response
        # codes (native/hostpath.cc). ``hot_lane=None`` means auto (on
        # when the library exports it); ``False`` pins the pure-Python
        # cached lane, which stays byte-identical (the fuzz parity suite
        # drives both).
        self._hot_lane = None
        #: quota-lease broker (lease/broker.py), attached by
        #: ``attach_lease`` when --lease-mode is on; None = lease tier
        #: off, byte-identical to the pre-lease lane.
        self.lease_broker = None
        #: pod frontend (server/peering.py PodFrontend), attached by
        #: ``attach_pod`` when this process serves inside a pod: the
        #: hot lane then splits batches into locally-owned rows (staged
        #: as ever) and foreign-owned rows bulk-forwarded to their
        #: owner host over the frontend's PeerLane. None = single-host,
        #: byte-identical to the pre-pod lane.
        self._pod = None
        #: cumulative lane stats carried across interner-recycle context
        #: swaps (the mirror dies with its context).
        self._lane_stats_base: Dict[str, int] = {}
        want_lane = True if hot_lane is None else bool(hot_lane)
        if (
            want_lane and self.plan_cache is not None
            and native.lane_available()
        ):
            self._hot_lane = self.hp.hot_lane(
                self.storage._scratch, cap=max(4 * max_batch, 1 << 14),
                max_rows=max(max_batch, 1 << 12),
            )
            self.plan_cache.add_mirror(self._hot_lane)

    @property
    def recorder(self):
        """Device-plane telemetry sink, shared with the compiled limiter
        (set_metrics on the limiter wires it — possibly after this
        pipeline is constructed; one flight recorder and one batch-id
        sequence per process). Memoized on first sight so the per-request
        gate in submit() costs an attribute read, not a getattr chain."""
        rec = self._recorder
        if rec is None:
            rec = getattr(self.limiter, "recorder", None)
            if rec is not None:
                self._recorder = rec
        return rec

    @property
    def _pending(self):
        """Aggregate pending queue across serving shards (stats/debug
        surface only — the hot path never builds this list)."""
        out: list = []
        for shard in list(self._shards.values()):
            out.extend(shard.pending)
        return out

    # -- plan management ----------------------------------------------------

    def invalidate(self) -> None:
        """Limits changed: drop all namespace plans (rebuilt lazily) and
        orphan every cached decision plan (epoch bump) — a limits change
        can never serve a stale template."""
        self._plans.clear()
        if self.plan_cache is not None:
            self.plan_cache.bump_epoch()

    def plan_cache_stats(self) -> dict:
        return self.plan_cache.stats() if self.plan_cache is not None else {}

    def lane_stats(self) -> dict:
        """Cumulative native hot-lane stats (C plan mirror + staging),
        carried across interner-recycle context swaps. Serialized under
        the native lock: begins mutate the C counters with the GIL
        released, and a recycle frees the context — an unguarded read
        from the metrics/debug thread would race both."""
        if self._hot_lane is None:
            return {}
        with self._native_lock:
            lane = self._hot_lane
            if lane is None:
                return {}
            stats = lane.stats()
            base = self._lane_stats_base
            return {
                key: stats[key] + base.get(key, 0)
                for key in ("hits", "misses", "staged_hits", "insertions",
                            "invalidations", "overflows", "foreign")
            } | {"plans": stats["plans"], "epoch": stats["epoch"]}

    def library_stats(self) -> dict:
        """Metrics poll surface for the plan_cache_*, native_lane_* and
        lease_* families."""
        out = dict(self.plan_cache_stats())
        lane_stats = self.lane_stats()
        if lane_stats:
            out.update({
                "native_lane_rows": lane_stats["hits"],
                "native_lane_misses": lane_stats["misses"],
                "native_lane_staged_hits": lane_stats["staged_hits"],
                "native_lane_invalidations": lane_stats["invalidations"],
                "native_lane_overflows": lane_stats["overflows"],
                "native_lane_plans": lane_stats["plans"],
            })
        if self.lease_broker is not None:
            out.update(self.lease_broker.stats())
        out.update(self.pod_stats())
        return out

    @property
    def hot_lane_active(self) -> bool:
        return self._hot_lane is not None

    # -- quota leasing (lease/broker.py) -------------------------------------

    def attach_lease(self, config=None, autostart: bool = True):
        """Stand up the quota-lease tier on this pipeline: a LeaseBroker
        that grants pre-debited token batches to hot mirrored plans, so
        repeat descriptors with live tokens are admitted in the C hot
        lane with zero device work. Requires the hot lane (the C mirror
        holds the balances). Epoch bumps wake the broker through the
        plan cache's release hooks so reload-stranded tokens settle
        promptly."""
        from ..lease import LeaseBroker

        if self._hot_lane is None:
            raise RuntimeError(
                "lease tier requires the native hot lane (plan mirror)"
            )
        if not native.lease_available():
            # A pre-lease binary exports the hot lane but none of the
            # hp_lease_* symbols: without this gate the tier would log
            # "on" while every broker call dies silently.
            raise RuntimeError(
                "native library lacks the lease exports (stale binary; "
                "rebuild native/hostpath.cc)"
            )
        if self.lease_broker is not None:
            return self.lease_broker
        broker = LeaseBroker(self, config)
        self.lease_broker = broker
        with self._native_lock:
            broker.attach_lane(self._hot_lane)
        if self.plan_cache is not None:
            self.plan_cache.on_epoch_bump = broker.poke
        if autostart:
            broker.start()
        return broker

    # -- pod fast path (ISSUE 13) --------------------------------------------

    def attach_pod(self, frontend) -> None:
        """Make the hot lane shard-aware: the C mirror learns the pod
        topology (hp_pod_config), every derived plan is stamped with
        its owner host (the C-side crc32 verdict for single-key plans,
        the router's verdict for pinned/multi-key ones), and begins
        answer foreign-owned rows as ``LANE_FOREIGN_BASE + owner`` so
        the flush bulk-forwards them over the frontend's PeerLane — one
        RPC per (owner, flush), not one per decision."""
        if self._hot_lane is None:
            raise RuntimeError(
                "pod mode requires the native hot lane (plan mirror)"
            )
        if not native.pod_available():
            raise RuntimeError(
                "native library lacks the pod ownership exports (stale "
                "binary; rebuild native/hostpath.cc)"
            )
        self._pod = frontend
        topo = frontend.router.topology
        with self._native_lock:
            self.hp.pod_config(
                topo.hosts, topo.host_id, topo.shards_per_host
            )

    def pod_stats(self) -> dict:
        """The C lane's local/foreign row split (pod_hot_* families);
        empty when not a pod."""
        if self._pod is None:
            return {}
        stats = self.lane_stats()
        return {
            "pod_hot_local_rows": stats.get("hits", 0),
            "pod_hot_foreign_rows": stats.get("foreign", 0),
        }

    def lease_stats(self) -> dict:
        """Lease-tier debug surface (/debug/stats ``lease`` section);
        empty when the tier is off."""
        broker = self.lease_broker
        if broker is None:
            return {}
        out = broker.stats()
        out["leases"] = len(broker._leases)
        return out

    def drain_leased_usage(self) -> Dict[int, int]:
        """Tenant usage observatory (ISSUE 8): per-SLOT counts of
        admissions answered from live leases since the last drain.
        Leased rows never reach the device's hit accumulator, so the
        observatory merges these in for full attribution. The C side
        reports per-plan (blob, count); each count lands on EVERY slot
        of the plan — exactly the per-hit accounting a kernel row would
        have produced. Resolution rides the Python plan cache under the
        native lock; a plan the cache has since evicted (the mirror may
        outlive it) drops its counts — bounded by one drain interval."""
        lane = self._hot_lane
        cache = self.plan_cache
        if lane is None or cache is None:
            return {}
        out: Dict[int, int] = {}
        with self._native_lock:
            if self._hot_lane is not lane:
                return {}
            drained = lane.usage_drain()
            if not drained:
                return {}
            entries = cache.entries
            for blob, count in drained:
                plan = entries.get(blob)
                if plan is None:
                    continue
                for slot in plan.slots:
                    out[slot] = out.get(slot, 0) + count
        return out

    def outstanding_lease_debit(self) -> Dict[int, int]:
        """Per-slot outstanding leased debit from the broker ledger
        (the observatory's over-admission context for /debug/top);
        empty with the tier off."""
        broker = self.lease_broker
        if broker is None:
            return {}
        return broker.outstanding_by_slot()

    def lane_code_templates(self) -> Optional[dict]:
        """(grpc status, payload) per hot-lane outcome code, for the
        native ingress's batch-coded respond path; None when the lane is
        off (the pump then keeps the per-row answer path). Pod mode
        also answers None: foreign-owned rows carry codes >= LANE_
        FOREIGN_BASE with no local template — the per-row submit path
        (whose flush owns the bulk-forward lane) must decide them."""
        if self._hot_lane is None or self._pod is not None:
            return None
        return {
            native.LANE_OK: (0, self.OK_BLOB),
            native.LANE_UNKNOWN: (0, self.UNKNOWN_BLOB),
            native.LANE_OVER: (0, self.OVER_BLOB),
        }

    def _plan_for(self, domain_token: int) -> Optional[_NsPlan]:
        plan = self._plans.get(domain_token, _MISSING_PLAN)
        if plan is not _MISSING_PLAN:
            return plan
        namespace = Namespace.of(self.hp.string(domain_token))
        pod = self._pod
        if pod is not None and pod._psum_serves(namespace):
            # Psum-served global namespace (ISSUE 13): decided by the
            # lockstep psum lane through the exact per-request path on
            # EVERY host — the columnar device lane must not count it a
            # second time against one host's table. None = exact path,
            # the same shape as a non-vectorizable namespace.
            self._plans[domain_token] = None
            return None
        limits = self.limiter.get_limits(namespace)
        compiler = NamespaceCompiler(limits, interner=self._interner)
        native_ok = compiler.fully_vectorized and all(
            # Limits the storage would route to its exact host fallback
            # (beyond-device-cap windows, non-ms-tick buckets) bypass the
            # columnar kernel — such namespaces take the exact path.
            # Device-eligible token buckets ride the fast path: their
            # hits carry the GCRA interval + bucket flag to the kernel.
            (
                limit.max_value <= K.MAX_VALUE_CAP
                if limit.policy == "fixed_window"
                else device_eligible(
                    limit.max_value, limit.seconds,
                    K.MAX_VALUE_CAP, K.WINDOW_MS_CAP,
                )
            )
            for limit in limits
        )
        if not limits or not native_ok:
            # Namespace needs the exact path (or has no limits -> cheap OK,
            # handled by an empty plan).
            plan = _NsPlan(namespace, compiler, self.hp) if not limits else None
        else:
            plan = _NsPlan(namespace, compiler, self.hp)
            for cl in compiler.limits:
                for key in cl.var_keys:
                    self._track(key)
                for m in cl.mask:
                    for key in m.keys:
                        self._track(key)
        self._plans[domain_token] = plan
        return plan

    def _track(self, key: str) -> None:
        if key not in self._tracked:
            self._tracked[key] = self.hp.track(key)

    # -- submission ----------------------------------------------------------

    def _shard_for(self, loop) -> _SubmitShard:
        shard = self._shards.get(loop)
        if shard is not None:
            return shard
        with self._shards_lock:
            shard = self._shards.get(loop)
            if shard is None:
                # Prune shards whose loop died so loop churn (tests,
                # new-loop-per-call embeddings) cannot leak shard
                # structs for the pipeline's lifetime.
                for dead in [l for l in self._shards if l.is_closed()]:
                    del self._shards[dead]
                shard = _SubmitShard(loop, self.max_inflight)
                self._shards[loop] = shard
            return shard

    def submit(self, blob: bytes) -> "asyncio.Future":
        """Enqueue one raw request on the calling loop's serving shard;
        returns the future of its response bytes. Plain function — no
        per-request coroutine, no task: the award of the sharded serving
        model is that a request costs one future and one list append
        before the batch machinery takes over."""
        loop = asyncio.get_running_loop()
        shard = self._shards.get(loop)
        if shard is None:
            shard = self._shard_for(loop)
        future = loop.create_future()
        adm = self._tpu.admission
        if adm is not None and adm.use_failover():
            # Device-plane breaker open: exact per-request path, whose
            # storage call lands on the host failover oracle.
            _spawn_detached(self._decide_exact(blob, future))
            return future
        # Timestamp unconditionally (a recorder attached between enqueue
        # and flush would otherwise read t=0.0 as a process-uptime-sized
        # queue wait); only the request-id capture is recorder-gated.
        shard.pending.append((
            blob, future, time.perf_counter(),
            current_request_id() if self.recorder is not None else None,
        ))
        task = shard.flush_task
        if task is None or task.done():
            shard.flush_task = _spawn_detached(self._flush_soon(shard))
        if len(shard.pending) == self.max_batch:
            # == not >=: the caller may enqueue a whole burst before the
            # loop runs any task — one size-flush per threshold crossing,
            # not one per submit past it.
            _spawn_detached(self._flush(shard, "size"))
        return future

    async def submit_async(self, blob: bytes) -> bytes:
        """Coroutine form of ``submit`` for callers that schedule
        cross-thread (``run_coroutine_threadsafe`` needs a coroutine)."""
        return await self.submit(blob)

    async def _flush_soon(self, shard: _SubmitShard) -> None:
        await asyncio.sleep(self.max_delay)
        await self._flush(shard)
        if shard.pending:
            shard.flush_task = _spawn_detached(self._flush_soon(shard))

    async def _flush(
        self, shard: _SubmitShard, reason: Optional[str] = None
    ) -> None:
        batch, shard.pending = shard.pending, []
        if not batch:
            return
        loop = asyncio.get_running_loop()
        rec = self.recorder
        t_flush = time.perf_counter()
        batch_id = 0
        if rec is not None:
            batch_id = rec.next_batch_id()
            try:
                rec.record_flush(
                    reason or (
                        "size" if len(batch) >= self.max_batch
                        else "deadline"
                    ),
                    len(batch) / self.max_batch,
                    [t_flush - t for _b, _f, t, _rid in batch],
                )
            except Exception:
                pass  # telemetry must never strand a batch's futures
        # Two-phase pipelining (the MicroBatcher pattern): the host phase
        # (plan cache -> parse -> masks -> slots -> kernel LAUNCH) runs on
        # the dispatch thread and returns without waiting on the device;
        # the collect phase (device_get -> resolve futures) runs on collect
        # threads. Batch N+1's host phase overlaps batch N's device round
        # trip — on TPU the round trip is the dominant term, so this is
        # where the serving-path ceiling moves from 8192/RTT to
        # 8192/host-time.
        adm = self._tpu.admission
        # Chunked pipelined dispatch (batcher.py ChunkPlanner): split the
        # flush into sub-batches riding the shard's inflight window —
        # chunk i+1's parse/stage/upload overlaps chunk i's device round
        # trip, so a request waits for its chunk, not the whole flush.
        ranges = self.chunk_planner.split(
            [1] * len(batch), chunk_queue_wait(adm, batch[0][2], t_flush)
        )
        if rec is not None:
            rec.record_chunks([hi - lo for lo, hi in ranges])
        # Every chunk registers as in-flight BEFORE any await, so a
        # breaker trip can fail chunks still waiting on the window (they
        # left shard.pending at the top of this flush).
        chunk_seqs = []
        for lo, hi in ranges:
            shard.batch_seq += 1
            shard.inflight_batches[shard.batch_seq] = batch[lo:hi]
            chunk_seqs.append(shard.batch_seq)

        def _drop_rest(idx, exc):
            """Fail (and deregister) chunk idx onward — nothing may be
            left silently stranded when this coroutine unwinds."""
            for (l2, h2), s2 in zip(ranges[idx:], chunk_seqs[idx:]):
                shard.inflight_batches.pop(s2, None)
                for _blob, future, _t, _rid in batch[l2:h2]:
                    if not future.done():
                        future.set_exception(exc)

        failed = None
        for ci, ((lo, hi), seq) in enumerate(zip(ranges, chunk_seqs)):
            sub = batch[lo:hi]
            if failed is not None:
                shard.inflight_batches.pop(seq, None)
                for _blob, future, _t, _rid in sub:
                    if not future.done():
                        future.set_exception(failed)
                continue
            try:
                await shard.sem.acquire()
            except BaseException as exc:
                # Cancellation (loop teardown) mid-flush must not strand
                # the chunks still waiting on the window.
                _drop_rest(ci, exc)
                raise
            t_submit = time.perf_counter()
            token = adm.breaker.batch_started() if adm is not None else 0
            try:
                ((results, slow_rows, pendings, foreign), t_begin, t_staged,
                 t_cache, t_lane) = (
                    await loop.run_in_executor(
                        self._dispatch_pool, self._timed_begin_batch,
                        [b for b, _f, _t, _rid in sub],
                    )
                )
            except BaseException as exc:
                shard.sem.release()
                if adm is not None:
                    adm.breaker.batch_finished(token, exc)
                if not isinstance(exc, Exception):
                    _drop_rest(ci, exc)
                    raise
                shard.inflight_batches.pop(seq, None)
                for _blob, future, _t, _rid in sub:
                    if not future.done():
                        future.set_exception(exc)
                failed = exc
                continue
            # Requests the columnar path couldn't take: exact per-request
            # path.
            for r in slow_rows:
                blob, future, _t, _rid = sub[r]
                _spawn_detached(self._decide_exact(blob, future))
            # Pod split (ISSUE 13): foreign-owned rows leave in ONE bulk
            # forward per owner per flush — the owner decides them on
            # ITS zero-Python lane and the payloads resolve the futures.
            for owner, rows in foreign.items():
                _spawn_detached(self._forward_bulk(
                    owner, [(sub[r][0], sub[r][1]) for r in rows]
                ))
            phases = {
                "dispatch": t_begin - t_submit,
                "host_cache": t_cache,
                "native_lane": t_lane,
                "host_stage": (t_staged - t_begin) - t_cache - t_lane,
            }
            task = loop.run_in_executor(
                self._collect_pool, self._finish_batch, sub, results,
                pendings, batch_id, t_flush, phases,
            )
            shard.inflight.add(task)

            def _collected(t, seq=seq, token=token, sub=sub):
                shard.inflight.discard(t)
                shard.inflight_batches.pop(seq, None)
                shard.sem.release()
                exc = t.exception()
                if adm is not None:
                    adm.breaker.batch_finished(token, exc)
                if exc is not None:
                    for _blob, future, _t, _rid in sub:
                        if not future.done():
                            future.set_exception(exc)

            task.add_done_callback(_collected)

    # -- the columnar fast path ----------------------------------------------

    def _recycle_context_if_needed(self) -> None:
        """Interner past the cap: swap in a fresh native context. Slot-map
        entries repopulate lazily through the Python key space. Decision
        plans survive: they pin Python-table slot indices and response
        templates, neither of which the interner owns."""
        if self.hp.interned_count() <= self.max_interned:
            return
        old = self.hp
        old_lane = self._hot_lane
        self.hp = native.HostPath()
        self._interner = self.hp.as_interner()
        self._tracked = {}
        self._plans = {}
        if self._pod is not None:
            # The fresh context must classify foreign rows from its
            # first begin — an un-armed mirror would stage (and decide
            # locally) keys other hosts own.
            topo = self._pod.router.topology
            self.hp.pod_config(
                topo.hosts, topo.host_id, topo.shards_per_host
            )
        # The storage lock spans the swap AND the free: slot-release
        # hooks fan out to the mirror list under this same lock, so no
        # release can reach the old lane's context after hp_free (and
        # lane_stats readers serialize on the native lock the caller
        # already holds). In-flight pendings keep the OLD lane object —
        # its finish pass is context-free (NULL ctx, per-call scratch,
        # string memos seeded at insertion), so it survives the close.
        with self.storage._lock:
            if old_lane is not None:
                # The mirror dies with its context: fold its cumulative
                # stats into the carried base and stand up a fresh lane.
                stats = old_lane.stats()
                base = self._lane_stats_base
                for key in ("hits", "misses", "staged_hits", "insertions",
                            "invalidations", "overflows", "foreign"):
                    base[key] = base.get(key, 0) + stats[key]
                self.plan_cache.remove_mirror(old_lane)
                self._hot_lane = self.hp.hot_lane(
                    self.storage._scratch, cap=old_lane.cap,
                    max_rows=old_lane.max_rows,
                )
                self.plan_cache.add_mirror(self._hot_lane)
                if self.lease_broker is not None:
                    # Leases die with the old mirror: reclaim + credit
                    # them before the context is freed, then re-arm the
                    # fresh lane's consume path.
                    self.lease_broker.on_context_swap(old_lane)
                    self.lease_broker.attach_lane(self._hot_lane)
            self.storage._table.native_keys.clear()
            self.storage._table.on_native_release = self.hp.slots_remove
            old.close()

    def decide_many(
        self, blobs: List[bytes], chunk: int = 8192, inflight: int = 8,
        forward: bool = True,
    ) -> List[Optional[bytes]]:
        """Synchronous bulk engine path: raw request blobs in, response
        blobs out, zero per-request asyncio. ``None`` marks rows the
        columnar path can't take (multi-descriptor requests, namespaces
        needing the exact path) — feed those through ``submit``; rows
        whose counter allocation failed come back as the distinct
        ``STORAGE_ERROR`` sentinel (answer UNAVAILABLE, don't retry
        through submit). Up to
        ``inflight`` chunks ride the device queue at once (JAX async
        dispatch), so the launch round trip streams instead of
        stalling per chunk; admission stays exact because
        launches thread the state array in order. This is the
        integration surface for a native ingress that owns its own
        socket loop.

        Pod mode: foreign-owned rows bulk-forward to their owner (one
        blocking lane RPC per owner per chunk); ``forward=False`` — the
        owner side of a bulk hop — answers them None instead, so an
        ownership skew can never ping-pong a row between hosts."""
        from collections import deque

        out: List[Optional[bytes]] = []
        window: deque = deque()  # (results, pendings, codes, part)
        lane = self._hot_lane
        # codes -> response template; LANE_MISS/LANE_KERNEL resolve via
        # ``results`` (bytes, STORAGE_ERROR, or None = slow). Object-
        # dtype fancy indexing keeps the steady-state (all-hot) batch
        # free of per-row Python.
        lut = np.array(
            [None, None, self.OK_BLOB, self.UNKNOWN_BLOB, self.OVER_BLOB,
             _STORAGE_ERROR],
            object,
        )
        base = native.LANE_FOREIGN_BASE

        def collect_oldest():
            results, pendings, codes, part = window.popleft()
            for p in pendings:
                self._finish_namespace(p, results)
            if codes is None:
                out.extend(results)
                return
            if self._pod is not None:
                fr = np.nonzero(codes >= base)[0]
                if fr.size:
                    if forward:
                        groups: Dict[int, List[int]] = {}
                        for i in fr.tolist():
                            groups.setdefault(
                                int(codes[i]) - base, []
                            ).append(i)
                        # submit every owner's hop before collecting
                        # any: the chunk pays max-of-RPC-latencies
                        # across owners, not sum.
                        hops = [
                            (rows, self._pod.forward_bulk_submit(
                                owner, [part[i] for i in rows]))
                            for owner, rows in groups.items()
                        ]
                        for rows, fut in hops:
                            payloads = self._pod.forward_bulk_collect(
                                fut, len(rows)
                            )
                            for i, payload in zip(rows, payloads):
                                results[i] = payload  # None = slow row
                    # forward=False (the owner side of a bulk hop):
                    # results stay None — the ORIGIN owns the fallback.
                    # Either way the codes must be lut-safe:
                    codes = np.where(
                        codes >= base, np.int8(native.LANE_MISS), codes
                    )
            vals = lut[codes]
            low = np.nonzero(codes < native.LANE_OK)[0]
            if low.size:  # miss-lane rows answer from results
                for i in low.tolist():
                    vals[i] = results[i]
            out.extend(vals.tolist())

        for ofs in range(0, len(blobs), chunk):
            part = blobs[ofs:ofs + chunk]
            with self._native_lock:
                if lane is not None:
                    # The hot lane moves the repeat-descriptor work —
                    # plan lookup, staging, response build — into ONE
                    # GIL-free C call, so the bulk engine path now DOES
                    # ride the (mirrored) plan cache: at engine chunk
                    # sizes the mirror's hash pass beats even the
                    # vectorized parse -> mask -> slot lane.
                    results, _slow, pendings, codes = (
                        self._begin_batch_coded_locked(part, use_cache=True)
                    )
                else:
                    # Pure-Python fallback: skip the plan cache — its
                    # per-row Python lookups lose to the vectorized
                    # parse lane at these chunk sizes.
                    results, _slow, pendings, _foreign = (
                        self._begin_batch_locked(part, use_cache=False)
                    )
                    codes = None
            window.append((results, pendings, codes, part))
            if len(window) > max(inflight, 1):
                collect_oldest()
        while window:
            collect_oldest()
        return out

    def _begin_batch(self, blobs: List[bytes]):
        with self._native_lock:
            return self._begin_batch_locked(blobs)

    def _begin_batch_coded_ptrs(self, ptrs, lens, n: int):
        """The ingress pump's zero-copy begin: the batch stays in the
        take buffers (ctypes pointer/length arrays) end to end — a
        repeat descriptor runs zero Python bytecode per row between the
        pump and the kernel launch. Returns (codes, results, slow_rows,
        pendings); only when the hot lane is active (the pump gates on
        ``lane_code_templates``)."""
        if self._hot_lane is None:
            raise RuntimeError("native hot lane is off")
        with self._native_lock:
            results, slow_rows, pendings, codes = (
                self._begin_batch_coded_locked(
                    None, True, ptrs=ptrs, lens=lens, count=n
                )
            )
        return codes, results, slow_rows, pendings

    def _timed_begin_batch(self, blobs: List[bytes]):
        """(begin result, t_start, t_end, host_cache_s, native_lane_s) —
        the dispatch-thread host phase with its executor-handoff,
        staging, plan-cache and hot-lane times exposed. The splits are
        read directly after the begin on the same thread; concurrent
        decide_many callers can at worst skew this telemetry split,
        never the results."""
        t_start = time.perf_counter()
        out = self._begin_batch(blobs)
        return (out, t_start, time.perf_counter(), self._last_host_cache,
                self._last_native_lane)

    def _begin_batch_locked(self, blobs: List[bytes], use_cache: bool = True):
        """Host phase, bytes-resolving form: the coded begin below plus
        response bytes for the rows the hot lane decided at begin time
        (the future-resolving submit path wants ``results`` rows, not
        codes). Hot kernel rows fill at finish (``fill_results``).
        ``foreign`` maps owner host -> batch rows the pod split
        classified as foreign-owned (empty outside pod mode): the
        caller bulk-forwards each group in ONE peer-lane RPC."""
        results, slow_rows, pendings, codes = self._begin_batch_coded_locked(
            blobs, use_cache
        )
        foreign: Dict[int, List[int]] = {}
        if codes is not None:
            ok_blob, unknown_blob = self.OK_BLOB, self.UNKNOWN_BLOB
            for r in np.nonzero(codes == native.LANE_OK)[0].tolist():
                results[r] = ok_blob
            for r in np.nonzero(codes == native.LANE_UNKNOWN)[0].tolist():
                results[r] = unknown_blob
            for pending in pendings:
                if type(pending) is _HotPending:
                    pending.staged.fill_results = True
            if self._pod is not None:
                base = native.LANE_FOREIGN_BASE
                for r in np.nonzero(codes >= base)[0].tolist():
                    foreign.setdefault(int(codes[r]) - base, []).append(r)
        return results, slow_rows, pendings, foreign

    def _begin_batch_coded_locked(
        self, blobs: Optional[List[bytes]], use_cache: bool = True,
        ptrs=None, lens=None, count: Optional[int] = None,
    ):
        """Host phase: hot-lane (or plan-cache) lookup, then
        parse/group/evaluate/slots for the misses, LAUNCH kernels for
        every staged lane. Returns (results, slow_rows, pendings,
        codes):

        - ``codes`` is the hot lane's per-row outcome column
          (native.LANE_*; None when the lane is off). Rows the lane
          decided stay None in ``results`` — the ingress pump answers
          them with ONE ``h2i_respond_coded`` call and the submit path
          converts codes to template bytes, so no per-row Python runs
          for a repeat descriptor between here and the kernel launch.
        - the miss lane fills ``results`` rows directly (bytes /
          STORAGE_ERROR), slow_rows lists exact-path rows (left None).
        - ``blobs`` may be None when ``ptrs``/``lens``/``count`` address
          the batch in place (the ingress's take buffers): only
          miss/slow rows materialize Python bytes then.

        ``use_cache=False`` (the legacy bulk engine path) skips lane,
        lookup and insertion. Callers hold ``_native_lock``."""
        n = count if blobs is None else len(blobs)
        adm = self._tpu.admission
        if adm is not None and adm.use_failover():
            # Breaker open: every row takes the exact path (whose
            # storage call fails over to the host oracle) — the
            # columnar path would launch kernels on the dead plane.
            self._last_host_cache = 0.0
            self._last_native_lane = 0.0
            return [None] * n, list(range(n)), [], None
        self._recycle_context_if_needed()
        results: List[Optional[bytes]] = [None] * n
        pendings: list = []
        slow_rows: List[int] = []

        cache = self.plan_cache if use_cache else None
        # Epoch snapshot BEFORE any plan derivation: inserts check it,
        # so a limits bump racing this batch on another thread discards
        # the then-stale plans instead of filing them under the new
        # epoch.
        cache_epoch = cache.epoch if cache is not None else 0
        lane = self._hot_lane if use_cache else None
        codes = None
        miss_idx: List[int] = []
        self._last_host_cache = 0.0
        self._last_native_lane = 0.0
        if lane is not None:
            # ---- lane 0: the zero-Python hot lane -----------------------
            # One GIL-free C call covers plan lookup, columnar staging
            # into the pre-allocated upload buffers (padding included)
            # and begin-time response codes; the storage lock spans
            # lookup -> launch so a concurrent LRU eviction cannot
            # recycle a plan-pinned slot in between (the mirror's
            # invalidate_slot fires under this same lock).
            t_lane0 = time.perf_counter()
            with self.storage._lock:
                if blobs is not None:
                    staged = lane.begin(blobs, cache_epoch)
                else:
                    staged = lane.begin_ptrs(ptrs, lens, n, cache_epoch)
                # Coded callers (ingress pump, decide_many) answer from
                # the code column — only the bytes-resolving wrapper
                # (_begin_batch_locked) flips this back on.
                staged.fill_results = False
                if staged.k:
                    inflight = self.storage.begin_check_columnar(
                        *lane.kernel_columns(staged.H)
                    )
                    pendings.append(_HotPending(staged, lane, inflight))
            codes = staged.codes
            self._last_native_lane = time.perf_counter() - t_lane0
            if staged.ok_aggr and self.metrics is not None:
                for ns, calls, hits in lane.ok_aggr_strings(staged.ok_aggr):
                    self.metrics.incr_authorized_calls(ns, n=calls)
                    self.metrics.incr_authorized_hits(ns, hits)
            miss_mask = codes == native.LANE_MISS
            n_miss = int(miss_mask.sum())
            # The mirror IS the decision-plan cache's lookup half when
            # the lane is on: account its hit/miss traffic there too, so
            # plan_cache_hit_ratio keeps meaning "requests served from a
            # memoized plan" regardless of which side did the lookup.
            cache.count(n - n_miss, n_miss)
            if n_miss == 0:
                return results, slow_rows, pendings, codes
            miss_idx = np.nonzero(miss_mask)[0].tolist()
            return self._begin_miss_lane(
                blobs, ptrs, lens, n, miss_idx, results, slow_rows,
                pendings, codes, cache, cache_epoch, lane,
            )

        # ---- lane 1: the hot-descriptor plan cache (pure Python) --------
        t_cache0 = time.perf_counter()
        if cache is not None:
            cached_rows: List[Tuple[int, DecisionPlan]] = []
            ok_blob = self.OK_BLOB
            unknown_blob = self.UNKNOWN_BLOB
            ok_calls: Dict[str, int] = {}
            ok_hits: Dict[str, int] = {}
            miss_append = miss_idx.append
            hit_append = cached_rows.append
            metrics = self.metrics
            # The storage lock spans lookup -> launch so a concurrent LRU
            # eviction cannot recycle a plan-pinned slot in between
            # (invalidate_slot fires under this same lock).
            with self.storage._lock:
                # Raw-dict lookups + one stats call for the whole batch:
                # a bound-method call and two counter increments per row
                # taxed the cached lane ~0.7µs/request.
                get = cache.entries.get
                for i, blob in enumerate(blobs):
                    plan = get(blob)
                    if plan is None:
                        miss_append(i)
                    elif plan.kind == PLAN_KERNEL:
                        hit_append((i, plan))
                    elif plan.kind == PLAN_OK:
                        results[i] = ok_blob
                        ns = plan.namespace
                        if ns is not None and metrics is not None:
                            ok_calls[ns] = ok_calls.get(ns, 0) + 1
                            ok_hits[ns] = ok_hits.get(ns, 0) + plan.delta
                    else:
                        results[i] = unknown_blob
                cache.count(n - len(miss_idx), len(miss_idx))
                if cached_rows:
                    pendings.append(self._begin_cached(cached_rows))
            if metrics is not None:
                for ns, calls in ok_calls.items():
                    metrics.incr_authorized_calls(ns, n=calls)
                    metrics.incr_authorized_hits(ns, ok_hits[ns])
        else:
            miss_idx = list(range(n))
        self._last_host_cache = time.perf_counter() - t_cache0
        if not miss_idx:
            return results, slow_rows, pendings, codes
        return self._begin_miss_lane(
            blobs, None, None, n, miss_idx, results, slow_rows, pendings,
            codes, cache, cache_epoch, None,
        )

    def _begin_miss_lane(
        self, blobs, ptrs, lens, n, miss_idx, results, slow_rows,
        pendings, codes, cache, cache_epoch, lane,
    ):
        """lane 2: the miss path (parse -> masks -> slots -> launch).
        ``miss_idx`` rows of the batch are parsed, derived, launched and
        memoized (Python cache + C mirror when ``lane`` is active);
        bytes materialize here when the batch arrived as raw pointers
        (``blobs`` None)."""
        full = len(miss_idx) == n
        if blobs is None:
            # Pointer-addressed batch (the ingress pump): only the miss
            # rows become Python bytes — the hot rows never did.
            sub = [
                ctypes.string_at(ptrs[i], lens[i]) for i in miss_idx
            ]
        elif full:
            sub = blobs
        else:
            sub = [blobs[i] for i in miss_idx]
        row_map = np.asarray(miss_idx, np.int32)
        domains, hits, cols, _ndesc, extra = self.hp.parse_batch(sub)

        # Group rows by domain token — vectorized: the per-row Python
        # dict/append loop profiled as the single largest host cost of
        # decide_many (131k dict ops per 4x32k rows).
        unknown = domains < 0
        for r in np.nonzero(unknown)[0].tolist():
            results[miss_idx[r]] = self.UNKNOWN_BLOB
            if cache is not None:
                cache.put(sub[r], _UNKNOWN_PLAN_SINGLETON, cache_epoch)
                if lane is not None:
                    lane.plan_put(
                        sub[r], cache_epoch, native.LANE_UNKNOWN, -1, 1, 1
                    )
        slow_mask = np.logical_and(~unknown, extra > 0)
        slow_rows.extend(row_map[np.nonzero(slow_mask)[0]].tolist())
        norm_idx = np.nonzero(
            np.logical_and(~unknown, ~slow_mask)
        )[0].astype(np.int32)
        groups: List[Tuple[int, np.ndarray]] = []
        if norm_idx.size:
            toks = domains[norm_idx]
            first = int(toks[0])
            if bool((toks == first).all()):  # common case: one namespace
                groups = [(first, norm_idx)]
            else:
                order = np.argsort(toks, kind="stable")
                si, st = norm_idx[order], toks[order]
                starts = np.nonzero(
                    np.concatenate([[True], st[1:] != st[:-1]])
                )[0]
                ends = np.append(starts[1:], st.size)
                groups = [
                    (int(st[a]), si[a:b]) for a, b in zip(starts, ends)
                ]

        for token, rows in groups:
            plan = self._plan_for(token)
            if plan is None:
                # results stay None (slow)
                slow_rows.extend(row_map[rows].tolist())
                continue
            if not plan.limits_meta:
                for r in rows.tolist():
                    results[miss_idx[r]] = self.OK_BLOB
                    if cache is not None:
                        # Metrics-free OK (the uncached empty-namespace
                        # branch counts nothing either): namespace None.
                        cache.put(
                            sub[r], _FREE_OK_PLAN_SINGLETON, cache_epoch
                        )
                        if lane is not None:
                            lane.plan_put(
                                sub[r], cache_epoch, native.LANE_OK, -1,
                                1, 1,
                            )
                continue
            pending = self._begin_namespace(
                plan, token, rows, hits, cols, results, sub, row_map,
                cache, cache_epoch, lane, codes,
            )
            if pending is not None:
                pendings.append(pending)
        return results, slow_rows, pendings, codes

    def _begin_cached(self, cached_rows) -> "_CachedPending":
        """Stage and launch the plan-cache lane: rows grouped by hit
        arity so a whole group's kernel columns come from ONE
        ``np.array`` over the plans' flat int records — no per-row numpy
        work. Kernel request ids follow BATCH ROW ORDER (one stable
        argsort restores it after the arity-grouped conversion): rows of
        this lane contending on one counter admit in arrival order,
        byte-identical to the C hot lane's staging. Caller holds the
        storage lock."""
        by_n: Dict[int, list] = {}
        for pos, pair in enumerate(cached_rows):
            by_n.setdefault(pair[1].nhits, []).append((pos, pair[1]))
        entries: List[Tuple[int, DecisionPlan]] = cached_rows
        slots_p: List[np.ndarray] = []
        deltas_p: List[np.ndarray] = []
        maxes_p: List[np.ndarray] = []
        windows_p: List[np.ndarray] = []
        bucket_p: List[np.ndarray] = []
        req_p: List[np.ndarray] = []
        for nh in sorted(by_n):
            group = by_n[nh]
            k = len(group)
            # Every record field fits int32 by construction (slots index
            # the table, maxes/windows are device-capped): convert the
            # whole group's flat tuples in ONE int32 pass.
            rec = np.array(
                [p.record for _pos, p in group], np.int32
            ).reshape(k, nh, 4)
            slots_p.append(rec[:, :, 0].ravel())
            maxes_p.append(rec[:, :, 1].ravel())
            windows_p.append(rec[:, :, 2].ravel())
            bucket_p.append(rec[:, :, 3].ravel().astype(bool))
            deltas_p.append(np.repeat(
                np.array([p.delta_capped for _pos, p in group], np.int32),
                nh,
            ))
            req_p.append(np.repeat(
                np.array([pos for pos, _p in group], np.int32), nh
            ))
        if len(slots_p) == 1:  # common case: uniform hit arity
            slots, deltas, maxes = slots_p[0], deltas_p[0], maxes_p[0]
            windows, req, bucket = windows_p[0], req_p[0], bucket_p[0]
            if req.size and not bool((req[:-1] <= req[1:]).all()):
                order = np.argsort(req, kind="stable")
                slots, deltas, maxes = (
                    slots[order], deltas[order], maxes[order]
                )
                windows, req, bucket = (
                    windows[order], req[order], bucket[order]
                )
        else:
            slots = np.concatenate(slots_p)
            deltas = np.concatenate(deltas_p)
            maxes = np.concatenate(maxes_p)
            windows = np.concatenate(windows_p)
            req = np.concatenate(req_p)
            bucket = np.concatenate(bucket_p)
            # restore batch row order (kernel req_ids must be
            # nondecreasing; same-request hits stay contiguous under the
            # stable sort)
            order = np.argsort(req, kind="stable")
            slots, deltas, maxes = slots[order], deltas[order], maxes[order]
            windows, req, bucket = windows[order], req[order], bucket[order]
        nhits = slots.shape[0]
        arrays = self.storage.pad_hits(
            (slots, deltas, maxes, windows, req,
             np.zeros(nhits, bool),  # cached slots are live, never fresh
             bucket),
            nhits,
        )
        inflight = self.storage.begin_check_columnar(*arrays)
        return _CachedPending(entries, inflight)

    def _finish_cached(self, pending: "_CachedPending", results) -> None:
        """Collect the plan-cache lane: fill response templates and
        replicate the uncached lane's metrics exactly (authorized
        calls/hits per namespace; first failing hit names the limit)."""
        admitted, hit_ok, _rem, _ttl = self.storage.finish_check_columnar(
            pending.inflight, with_remaining=False
        )
        ok_blob, over_blob = self.OK_BLOB, self.OVER_BLOB
        metrics = self.metrics
        entries = pending.entries
        admitted_l = admitted[:len(entries)].tolist()
        if metrics is None:
            for (row, _plan), ok in zip(entries, admitted_l):
                results[row] = ok_blob if ok else over_blob
            return
        ok_calls: Dict[str, int] = {}
        ok_hits: Dict[str, int] = {}
        limited: Dict[Tuple[str, Optional[str]], int] = {}
        base = 0
        for (row, plan), ok in zip(entries, admitted_l):
            if ok:
                results[row] = ok_blob
                ns = plan.namespace
                ok_calls[ns] = ok_calls.get(ns, 0) + 1
                ok_hits[ns] = ok_hits.get(ns, 0) + plan.delta
            else:
                results[row] = over_blob
                name = None
                for j in range(plan.nhits):
                    if not hit_ok[base + j]:
                        name = plan.limit_names[j]
                        break
                key = (plan.namespace, name)
                limited[key] = limited.get(key, 0) + 1
            base += plan.nhits
        for ns, calls in ok_calls.items():
            metrics.incr_authorized_calls(ns, n=calls)
            metrics.incr_authorized_hits(ns, ok_hits[ns])
        for (ns, name), count in limited.items():
            metrics.incr_limited_calls(ns, name, n=count)

    def _finish_batch(
        self, batch, results, pendings, batch_id: int = 0,
        t_flush: float = 0.0, phases: Optional[dict] = None,
    ) -> None:
        """Collect phase: block on the device results, fill the kernel-
        decided rows, resolve every settled future in ONE loop callback
        (a call_soon_threadsafe per future is a self-pipe write + wakeup
        per request — it profiled as ~45% of the serving path)."""
        with device_batch_span(
            batch_id, len(batch), _native_trace_attrs(pendings)
        ) as span_phases:
            t_fin = time.perf_counter()
            for pending in pendings:
                self._finish_namespace(pending, results)
            t_done = time.perf_counter()
            # None marks slow-path rows (resolved later); note UNKNOWN
            # serializes to b"" (all-default proto3), which is a valid
            # response — only None is the sentinel. All futures of a
            # shard's batch were created on that shard's loop (submit is
            # loop-affine), so the whole batch resolves with ONE
            # call_soon_threadsafe.
            pairs = [
                (future, out)
                for (_blob, future, _t, _rid), out in zip(batch, results)
                if out is not None
            ]
            if pairs:
                pairs[0][0].get_loop().call_soon_threadsafe(
                    _resolve_many, pairs
                )
            rec = self.recorder
            if phases is None:
                return
            phases["device_sync"] = t_done - t_fin
            self.chunk_planner.observe(phases["device_sync"], len(batch))
            phases["unpack"] = time.perf_counter() - t_done
            span_phases(phases)
            if rec is None:
                return
            rec.record_batch(
                (
                    (t_enq, rid, None)
                    for (_blob, _future, t_enq, rid), out
                    in zip(batch, results)
                    if out is not None  # slow-path rows decided elsewhere
                ),
                batch_id, t_flush, phases,
            )

    def _begin_namespace(
        self, plan, token, rows, hits, cols, results, blobs, row_map,
        cache=None, cache_epoch=0, lane=None, codes=None,
    ) -> Optional["_NsPending"]:
        """rows index into the parse arrays (the miss subset); row_map
        maps them to positions in the submitted batch, which is what
        ``results`` rows and pendings speak. ``cache`` is the decision-
        plan cache to memoize this group's rows into — None on the bulk
        engine path, which must not pay the per-row insertion loop;
        ``lane`` additionally mirrors the plans into the C hot lane.
        In pod mode (``attach_pod``) rows whose counters another host
        owns are NOT staged here: their batch code flips to
        ``LANE_FOREIGN_BASE + owner`` (the caller bulk-forwards them)
        and their plan is memoized as foreign so every later repeat is
        classified by the C lane with zero Python."""
        rows_arr = np.asarray(rows, np.int32)
        m = rows_arr.shape[0]
        grows = row_map[rows_arr]  # global (batch) row per group row
        needed = set()
        for cl in plan.compiler.limits:
            needed.update(cl.var_keys)
            for mask in cl.mask:
                needed.update(mask.keys)
        if any(k not in cols for k in needed):
            # First batch for this namespace: its keys were tracked after
            # the batch-wide parse. Re-parse just this group.
            _d, h2, cols_local, _n, _e = self.hp.parse_batch(
                [blobs[r] for r in rows]
            )
            group_cols = {k: cols_local[k] for k in needed}
            deltas_req = h2
        else:
            group_cols = {k: cols[k][rows_arr] for k in needed}
            deltas_req = hits[rows_arr]

        # Pod routing at derivation time (ISSUE 13): one pass over the
        # applies-masks resolves each row's counter keys and the router
        # verdict — miss-path-only Python (once per unique blob; every
        # repeat rides the C-side owner stamp).
        pod = self._pod
        evaluated = None
        foreign_owner: Dict[int, int] = {}   # group-local row -> owner
        row_key_repr: Dict[int, bytes] = {}  # single-key rows: repr bytes
        if pod is not None:
            evaluated = list(plan.compiler.evaluate_columns(group_cols, m))
            row_keys: Dict[int, list] = {}
            for (cl, applies, var_cols), meta in zip(
                evaluated, plan.limits_meta
            ):
                limit = meta[4]
                idx_l = np.nonzero(applies)[0].tolist()
                if not idx_l:
                    continue
                ident = limit._identity
                var_sources = [v.source for v in limit.variables]
                for local in idx_l:
                    # the exact tuple counter_key() derives: identity +
                    # sorted (source, value) items (Counter sorts its
                    # set_variables — BTreeMap semantics)
                    set_vars = sorted(
                        (src, self.hp.string(int(var_cols[j][local])))
                        for j, src in enumerate(var_sources)
                    )
                    row_keys.setdefault(local, []).append(
                        (ident, tuple(set_vars))
                    )
            router = pod.router
            me = router.topology.host_id
            ns_str = str(plan.namespace)
            base = native.LANE_FOREIGN_BASE
            # Stamping authority: a PINNED namespace's owner is the
            # router's pin verdict — the key hash would disagree with
            # it (a pinned row's key may hash anywhere), so only
            # un-pinned single-key plans stamp through the C-side
            # crc32 (repr bytes below); pinned plans stamp the
            # resolved pin via plan_set_owner.
            ns_pinned = router.pinned_host(ns_str) is not None
            for local, keys in row_keys.items():
                _verdict, owner = router.verdict(ns_str, keys)
                if len(keys) == 1 and not ns_pinned:
                    row_key_repr[local] = repr(keys[0]).encode()
                if owner != me:
                    foreign_owner[local] = owner
                    if codes is not None:
                        codes[grows[local]] = base + owner

        hit_slots: List[np.ndarray] = []
        hit_deltas: List[np.ndarray] = []
        hit_maxes: List[np.ndarray] = []
        hit_windows: List[np.ndarray] = []
        hit_req: List[np.ndarray] = []
        hit_fresh: List[np.ndarray] = []
        hit_bucket: List[np.ndarray] = []
        hit_name: List[Tuple[object, np.ndarray]] = []  # (limit, local req idx)
        failed_reqs: set = set()  # local idx whose allocation errored
        # per-local-row flat plan records (slot, max, win, bucket) in
        # limit compile order, grown only on the miss path
        row_recs: Dict[int, list] = {}
        row_names: Dict[int, list] = {}
        row_ntoks: Dict[int, list] = {}

        # Lookup -> (alloc misses) -> kernel happens under the storage lock
        # so a concurrent LRU eviction cannot recycle a looked-up slot
        # between lookup and kernel (check_columnar re-enters the RLock).
        with self.storage._lock:
            # Phase 1: evaluate + resolve slots for EVERY limit before
            # building hit arrays — a late allocation failure must void the
            # failed request's deltas on earlier limits too (all-or-nothing).
            staged = []
            for (cl, applies, var_cols), meta in zip(
                evaluated if evaluated is not None
                else plan.compiler.evaluate_columns(group_cols, m),
                plan.limits_meta,
            ):
                limit_token, max_value, window_s, name, limit, ntok = meta
                if foreign_owner:
                    # foreign rows stage nothing locally — their owner
                    # decides them (and owns their device slots)
                    applies = applies.copy()
                    applies[list(foreign_owner)] = False
                idx = np.nonzero(applies)[0].astype(np.int32)
                if idx.size == 0:
                    continue
                k = 2 + len(var_cols)
                keys = np.empty((idx.size, k), np.int32)
                keys[:, 0] = token
                keys[:, 1] = limit_token
                for j, vc in enumerate(var_cols):
                    keys[:, 2 + j] = vc[idx]
                slots = self.hp.slots_lookup(keys)
                fresh = slots < 0
                if fresh.any():
                    self._allocate_missing(
                        limit, var_cols, idx, keys, slots, fresh, failed_reqs
                    )
                    # failed allocations leave slot -1: point them at the
                    # inert scratch cell with delta 0
                    bad = slots < 0
                    slots[bad] = self.storage._scratch
                    fresh[bad] = False
                staged.append((limit, idx, slots, fresh, max_value, window_s,
                               name, ntok))

            # Phase 2: build hit arrays with failed requests fully voided.
            for (limit, idx, slots, fresh, max_value, window_s, name,
                 ntok) in staged:
                hit_slots.append(slots.astype(np.int32))
                deltas_l = np.minimum(
                    deltas_req[idx], K.MAX_DELTA_CAP
                ).astype(np.int32)
                if failed_reqs:
                    deltas_l[np.isin(idx, list(failed_reqs))] = 0
                hit_deltas.append(deltas_l)
                hit_maxes.append(
                    np.full(idx.size, max_value, np.int32)
                )
                if limit.policy == "token_bucket":
                    win = emission_interval_ms(max_value, window_s)
                    is_bucket = True
                else:
                    win = min(window_s * 1000, 2**31 - 2**30 - 2)
                    is_bucket = False
                hit_windows.append(np.full(idx.size, win, np.int32))
                hit_req.append(idx)
                hit_fresh.append(fresh)
                hit_bucket.append(np.full(idx.size, is_bucket, bool))
                hit_name.append((limit, idx))
                if cache is not None:
                    ib = int(is_bucket)
                    mv = int(max_value)
                    slots_l = slots.tolist()
                    for pos, local in enumerate(idx.tolist()):
                        row_recs.setdefault(local, []).extend(
                            (slots_l[pos], mv, win, ib)
                        )
                        row_names.setdefault(local, []).append(name)
                        row_ntoks.setdefault(local, []).append(ntok)

            namespace = str(plan.namespace)
            if cache is not None:
                self._insert_plans(
                    cache, cache_epoch, blobs, rows_arr, deltas_req,
                    failed_reqs, row_recs, row_names, namespace, m,
                    lane, token, row_ntoks, foreign_owner, row_key_repr,
                )
            if not hit_slots:
                # Foreign rows answer on their owner host — neither the
                # OK template nor the metrics are this host's to emit.
                ok_locals = (
                    [l for l in range(m) if l not in foreign_owner]
                    if foreign_owner else range(m)
                )
                n_ok = 0
                for l in ok_locals:
                    results[grows[l]] = self.OK_BLOB
                    n_ok += 1
                if self.metrics and n_ok:
                    deltas_l = (
                        deltas_req if not foreign_owner
                        else deltas_req[
                            [l for l in range(m) if l not in foreign_owner]
                        ]
                    )
                    self.metrics.incr_authorized_calls(namespace, n=n_ok)
                    self.metrics.incr_authorized_hits(
                        namespace, int(deltas_l.sum())
                    )
                return None

            slots = np.concatenate(hit_slots)
            deltas = np.concatenate(hit_deltas)
            maxes = np.concatenate(hit_maxes)
            windows = np.concatenate(hit_windows)
            req = np.concatenate(hit_req)
            fresh = np.concatenate(hit_fresh)
            bucket = np.concatenate(hit_bucket)
            # Kernel req ids must be dense in [0, H): requests without hits
            # don't participate, so compress local indices.
            order = np.argsort(req, kind="stable")
            participating, kernel_req = np.unique(
                req[order], return_inverse=True
            )
            arrays = self.storage.pad_hits(
                (slots[order], deltas[order], maxes[order], windows[order],
                 kernel_req.astype(np.int32), fresh[order], bucket[order]),
                slots.shape[0],
            )
            inflight = self.storage.begin_check_columnar(*arrays)
        return _NsPending(
            namespace, grows, deltas_req, failed_reqs, participating,
            order, req, hit_name, inflight,
            foreign_locals=frozenset(foreign_owner),
        )

    def _insert_plans(
        self, cache, cache_epoch, blobs, rows_arr, deltas_req,
        failed_reqs, row_recs, row_names, namespace, m,
        lane=None, ns_token=-1, row_ntoks=None, foreign_owner=None,
        row_key_repr=None,
    ) -> None:
        """Memoize this group's miss rows: kernel plans for rows with
        resolved hits, OK plans for rows no limit applied to — into the
        Python cache and, when ``lane`` is active, the C plan mirror
        (stride-5 records: the stride-4 python record plus the limit-name
        token the hot finish aggregates limited calls by). Caller holds
        the storage lock (slot liveness).

        Pod mode: ``foreign_owner`` rows memoize as FOREIGN plans (no
        local slots — the counters live remote) and every mirrored plan
        is stamped with its owner. Single-key plans stamp through
        ``plan_stamp_owner`` — the C-side crc32 is the authority — so a
        repeat descriptor's whole ownership verdict runs in C."""
        rows_l = rows_arr.tolist()
        deltas_l = deltas_req.tolist() if hasattr(
            deltas_req, "tolist") else list(deltas_req)
        foreign_owner = foreign_owner or {}
        row_key_repr = row_key_repr or {}
        for local in range(m):
            if local in failed_reqs:
                continue
            delta = int(deltas_l[local])
            recs = row_recs.get(local)
            blob = blobs[rows_l[local]]
            owner = foreign_owner.get(local)
            if owner is not None:
                cache.put(blob, DecisionPlan(
                    PLAN_FOREIGN, namespace=namespace, delta=delta,
                    owner=owner,
                ), cache_epoch)
                if lane is not None:
                    lane.plan_put(
                        blob, cache_epoch, native.LANE_FOREIGN, ns_token,
                        delta, min(delta, K.MAX_DELTA_CAP), ns=namespace,
                    )
                    key_repr = row_key_repr.get(local)
                    if key_repr is not None:
                        lane.plan_stamp_owner(blob, cache_epoch, key_repr)
                    else:
                        lane.plan_set_owner(blob, cache_epoch, owner)
                continue
            if recs is None:
                cache.put(blob, DecisionPlan(
                    PLAN_OK, namespace=namespace, delta=delta,
                ), cache_epoch)
                if lane is not None:
                    lane.plan_put(
                        blob, cache_epoch, native.LANE_OK, ns_token,
                        delta, min(delta, K.MAX_DELTA_CAP), ns=namespace,
                    )
            else:
                record = tuple(recs)
                cache.put(blob, DecisionPlan(
                    PLAN_KERNEL,
                    namespace=namespace,
                    delta=delta,
                    delta_capped=min(delta, K.MAX_DELTA_CAP),
                    record=record,
                    limit_names=tuple(row_names[local]),
                    slots=record[0::4],
                ), cache_epoch)
                if lane is not None:
                    ntoks = row_ntoks[local]
                    rec4 = np.asarray(recs, np.int32).reshape(-1, 4)
                    rec5 = np.empty((rec4.shape[0], 5), np.int32)
                    rec5[:, :4] = rec4
                    rec5[:, 4] = ntoks
                    lane.plan_put(
                        blob, cache_epoch, native.LANE_KERNEL, ns_token,
                        delta, min(delta, K.MAX_DELTA_CAP), rec5,
                        ns=namespace,
                        names=zip(ntoks, row_names[local]),
                    )
                    if self._pod is not None:
                        # Stamp locally-owned single-key plans too: the
                        # C crc32 is the ownership authority end to end
                        # (a stamp of our own host id is a no-op split).
                        key_repr = row_key_repr.get(local)
                        if key_repr is not None:
                            lane.plan_stamp_owner(
                                blob, cache_epoch, key_repr
                            )

    def _finish_hot(self, pending: "_HotPending", results) -> None:
        """Collect the zero-Python hot lane: ONE C call turns the device
        result columns into final response codes (in place on the
        staged code column) and the batch's aggregated metrics. Response
        bytes materialize only for the future-resolving submit path
        (``fill_results``) — the ingress pump answers straight from the
        codes."""
        staged = pending.staged
        admitted, hit_ok, _rem, _ttl = self.storage.finish_check_columnar(
            pending.inflight, with_remaining=False
        )
        ok_aggr, limited = pending.lane.finish(staged, admitted, hit_ok)
        if staged.fill_results:
            ok_blob, over_blob = self.OK_BLOB, self.OVER_BLOB
            for r, a in zip(staged.rows.tolist(),
                            admitted[:staged.k].tolist()):
                results[r] = ok_blob if a else over_blob
        metrics = self.metrics
        if metrics is not None:
            for ns, calls, hits in ok_aggr:
                metrics.incr_authorized_calls(ns, n=calls)
                metrics.incr_authorized_hits(ns, hits)
            for ns, name, count in limited:
                metrics.incr_limited_calls(ns, name, n=count)

    def _finish_namespace(self, pending, results) -> None:
        """Collect one pending's device result and fill its rows (the
        miss-lane namespace pendings, the plan-cache lane and the native
        hot lane)."""
        if type(pending) is _HotPending:
            self._finish_hot(pending, results)
            return
        if type(pending) is _CachedPending:
            self._finish_cached(pending, results)
            return
        namespace = pending.namespace
        rows = pending.rows
        deltas_req = pending.deltas_req
        failed_reqs = pending.failed_reqs
        participating = pending.participating
        order = pending.order
        req = pending.req
        hit_name = pending.hit_name
        admitted, hit_ok, _rem, _ttl = self.storage.finish_check_columnar(
            pending.inflight, with_remaining=False
        )
        # Requests without hits default to admitted (no counter applied);
        # fill via flat arrays — the per-row dict build/get profiled as
        # the second-largest host cost of decide_many.
        m = len(rows)
        foreign_locals = pending.foreign_locals
        admitted_full = np.ones(m, bool)
        admitted_full[participating] = admitted[: participating.size]
        ok_blob, over_blob = self.OK_BLOB, self.OVER_BLOB
        rows_list = rows.tolist() if isinstance(rows, np.ndarray) else rows
        for local, (r, a) in enumerate(
            zip(rows_list, admitted_full.tolist())
        ):
            if local in foreign_locals:
                continue  # pod: the owner host answers this row
            results[r] = ok_blob if a else over_blob
        ok_mask = admitted_full
        if failed_reqs or foreign_locals:
            excluded = sorted(failed_reqs | set(foreign_locals))
            for local in sorted(failed_reqs):
                results[rows_list[local]] = _STORAGE_ERROR
            ok_mask = admitted_full.copy()
            ok_mask[excluded] = False
        n_ok = int(ok_mask.sum())
        ok_hits = int(deltas_req[ok_mask].sum())
        limited_rows = [
            local for local in np.nonzero(~admitted_full)[0].tolist()
            if local not in failed_reqs and local not in foreign_locals
        ]
        if self.metrics:
            if n_ok:
                self.metrics.incr_authorized_calls(namespace, n=n_ok)
                self.metrics.incr_authorized_hits(namespace, ok_hits)
            for local in limited_rows:
                # first failing hit in request order names the limit
                name = None
                pos = np.nonzero(req[order] == local)[0]
                for p in pos:
                    if not hit_ok[p]:
                        # recover the limit via cumulative spans
                        offset = 0
                        for limit, idx in hit_name:
                            if order[p] < offset + idx.size:
                                name = limit.name
                                break
                            offset += idx.size
                        break
                self.metrics.incr_limited_calls(namespace, name)

    def _allocate_missing(
        self, limit, var_cols, idx, keys, slots, fresh_mask, failed_reqs
    ) -> None:
        """Slot-map misses: allocate through the storage's key space (so
        LRU/eviction bookkeeping stays authoritative) and mirror into the
        native map. A per-counter StorageError fails only its own request
        (recorded in ``failed_reqs``), never the batch. Caller holds the
        storage lock."""
        var_sources = [v.source for v in limit.variables]
        storage = self.storage
        for pos in np.nonzero(fresh_mask)[0]:
            set_vars = {
                src: self.hp.string(int(var_cols[j][idx[pos]]))
                for j, src in enumerate(var_sources)
            }
            counter = Counter(limit, set_vars)
            try:
                slot, is_fresh = storage._slot_for(counter, create=True)
            except StorageError:
                failed_reqs.add(int(idx[pos]))
                continue
            # The key may already live in the Python key space (counter
            # created via the per-request path): then the cell is LIVE
            # and must not be reset by the fresh flag.
            fresh_mask[pos] = is_fresh
            key = keys[pos].copy()
            self.hp.slots_insert(key, slot)
            storage._table.native_keys[slot] = key
            slots[pos] = slot

    # -- exact fallback --------------------------------------------------------

    async def _decide_exact(self, blob: bytes, future: asyncio.Future) -> None:
        from ..server.rls import _context_from_request, _hits_addend

        try:
            req = self._pb.RateLimitRequest.FromString(blob)
            if not req.domain:
                out = self.UNKNOWN_BLOB
            else:
                ctx = _context_from_request(req)
                result = await self.limiter.check_rate_limited_and_update(
                    req.domain, ctx, _hits_addend(req), False
                )
                namespace = req.domain
                if result.limited:
                    if self.metrics:
                        self.metrics.incr_limited_calls(
                            namespace, result.limit_name
                        )
                    out = self.OVER_BLOB
                else:
                    if self.metrics:
                        self.metrics.incr_authorized_calls(namespace)
                        self.metrics.incr_authorized_hits(
                            namespace, _hits_addend(req)
                        )
                    out = self.OK_BLOB
            if not future.done():
                future.set_result(out)
        except Exception as exc:
            if not future.done():
                future.set_exception(exc)

    async def _forward_bulk(self, owner: int, pairs) -> None:
        """Resolve a flush's foreign-owned rows through ONE peer-lane
        bulk forward (ISSUE 13). ``pairs`` is [(blob, future)]. A dead
        or refusing owner never fails the rows outright: each falls
        back to the exact per-request path, whose limiter is the pod
        frontend — its breaker / degraded-owner stand-in machinery owns
        that failure mode (zero lost decisions across a partition)."""
        pod = self._pod
        payloads = None
        try:
            payloads = await pod.forward_bulk(
                owner, [blob for blob, _f in pairs]
            )
        except Exception:
            payloads = None
        if payloads is None or len(payloads) != len(pairs):
            for blob, future in pairs:
                if not future.done():
                    _spawn_detached(self._decide_exact(blob, future))
            return
        for (blob, future), payload in zip(pairs, payloads):
            if future.done():
                continue
            if payload is None:
                # the owner could not decide this row terminally
                # (its own verdict disagreed mid-reload, or the row
                # needs its exact path): one frontend-routed fallback
                _spawn_detached(self._decide_exact(blob, future))
            else:
                future.set_result(payload)

    async def decide_blobs_for_peer(self, blobs: List[bytes]):
        """Owner side of a bulk forward: decide raw blobs against the
        LOCAL plane — one ``decide_many`` pass (the zero-Python lane at
        bulk batch sizes), with ``forward=False`` so a row this host
        ALSO considers foreign (an ownership skew mid-reload) comes
        back None instead of ping-ponging; the origin falls back to its
        terminal per-request hop. Rows the columnar path can't take or
        whose allocation failed also answer None — the origin's exact
        path gives them their full semantics (priority, failover)."""
        loop = asyncio.get_running_loop()
        results = await loop.run_in_executor(
            None, lambda: self.decide_many(blobs, forward=False)
        )
        return [
            None if out is None or out is _STORAGE_ERROR else out
            for out in results
        ]

    def fail_over_queued(self, decider, exc) -> None:
        """Admission-plane breaker trip: queued raw requests re-route
        through the exact per-request path (which lands on the host
        failover oracle); dispatched-but-uncollected batches fail with
        ``exc``. ``decider`` is unused — the exact path already decides
        through the storage's failover branch. Thread-safe; fans out to
        every serving shard's loop."""
        for shard in list(self._shards.values()):
            loop = shard.loop
            if loop is None or loop.is_closed():
                continue

            def _drain(shard=shard):
                batch, shard.pending = shard.pending, []
                for blob, future, _t, _rid in batch:
                    if not future.done():
                        _spawn_detached(self._decide_exact(blob, future))
                for stuck in list(shard.inflight_batches.values()):
                    for _blob, future, _t, _rid in stuck:
                        if not future.done():
                            future.set_exception(exc)

            try:
                loop.call_soon_threadsafe(_drain)
            except RuntimeError:
                pass  # loop closed between the check and the call

    async def _close_shard(self, shard: _SubmitShard) -> None:
        await self._flush(shard, "shutdown")
        if shard.inflight:
            await asyncio.gather(*shard.inflight, return_exceptions=True)

    async def close(self) -> None:
        if self.lease_broker is not None:
            self.lease_broker.close()
        cur = asyncio.get_running_loop()
        for shard in list(self._shards.values()):
            if shard.loop is cur:
                await self._close_shard(shard)
            elif not shard.loop.is_closed() and shard.loop.is_running():
                try:
                    asyncio.run_coroutine_threadsafe(
                        self._close_shard(shard), shard.loop
                    ).result(timeout=10)
                except Exception:
                    pass  # shard loop died mid-shutdown: futures are gone
        self._dispatch_pool.shutdown(wait=False)
        self._collect_pool.shutdown(wait=False)


def _native_trace_attrs(pendings) -> Optional[dict]:
    """Span attributes for a 1-in-N sampled hot-lane batch (native
    telemetry plane): the trace id hp_hot_begin stamped plus the native
    begin splits, so an OTLP trace of a sampled zero-Python batch shows
    where native time went. None (zero cost) unless an exporter is
    installed AND this batch was sampled."""
    if not tracing_enabled():
        return None
    for pending in pendings:
        if type(pending) is _HotPending and pending.staged.trace_id:
            return native.staged_trace_attrs(pending.staged)
    return None


def _spawn_detached(coro) -> asyncio.Task:
    """Background task in a FRESH contextvars context. The spawn point
    can sit inside a request's MetricsLayer span (submit is awaited under
    the handler's should_rate_limit span): inheriting that context would
    parent the flush loop — and every slow-path decide it fans out — under
    one arbitrary request's span, folding other requests' storage time
    into its aggregate. Slow-path requests are measured by their own
    handler spans around the awaited future instead."""
    loop = asyncio.get_running_loop()
    if sys.version_info >= (3, 11):
        return loop.create_task(coro, context=contextvars.Context())
    # Python 3.10: create_task has no context kwarg, but Task captures
    # copy_context() at construction — run it inside the fresh context.
    return contextvars.Context().run(loop.create_task, coro)


def _resolve(future: asyncio.Future, value: bytes) -> None:
    if not future.done():
        future.set_result(value)


def _reject(future: asyncio.Future, exc: Exception) -> None:
    if not future.done():
        future.set_exception(exc)


def _resolve_many(pairs) -> None:
    for future, out in pairs:
        if future.done():
            continue
        if out is _STORAGE_ERROR:
            future.set_exception(
                StorageError("counter allocation failed", transient=True)
            )
        else:
            future.set_result(out)


class _NsPending:
    """One namespace's launched-but-uncollected kernel: everything
    ``_finish_namespace`` needs to turn the device result into response
    blobs and metrics. ``rows`` are batch-global row indices."""

    __slots__ = (
        "namespace", "rows", "deltas_req", "failed_reqs", "participating",
        "order", "req", "hit_name", "inflight", "foreign_locals",
    )

    def __init__(
        self, namespace, rows, deltas_req, failed_reqs, participating,
        order, req, hit_name, inflight, foreign_locals=frozenset(),
    ):
        self.namespace = namespace
        self.rows = rows
        self.deltas_req = deltas_req
        self.failed_reqs = failed_reqs
        self.participating = participating
        self.order = order
        self.req = req
        self.hit_name = hit_name
        self.inflight = inflight
        # pod: group-local rows decided by their owner host — the
        # finish pass must not fill (or count) them
        self.foreign_locals = foreign_locals


class _CachedPending:
    """The plan-cache lane's launched-but-uncollected kernel: entries in
    kernel request-id order, each (batch row, DecisionPlan)."""

    __slots__ = ("entries", "inflight")

    def __init__(self, entries, inflight):
        self.entries = entries
        self.inflight = inflight


class _HotPending:
    """The native hot lane's launched-but-uncollected kernel: the
    staged geometry/code column plus the lane that staged it (pinned so
    a pending survives an interner-recycle lane swap — its finish pass
    is context-free)."""

    __slots__ = ("staged", "lane", "inflight")

    def __init__(self, staged, lane, inflight):
        self.staged = staged
        self.lane = lane
        self.inflight = inflight


class _Missing:
    pass


_MISSING_PLAN = _Missing()
_STORAGE_ERROR = _Missing()
NativeRlsPipeline.STORAGE_ERROR = _STORAGE_ERROR
#: shared trivial plans (stateless: no slots, no metrics mutation)
_UNKNOWN_PLAN_SINGLETON = DecisionPlan(PLAN_UNKNOWN)
_FREE_OK_PLAN_SINGLETON = DecisionPlan(PLAN_OK, namespace=None)
