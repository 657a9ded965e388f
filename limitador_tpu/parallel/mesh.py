"""Multi-chip sharded counter table.

TPU-native analogue of the reference's counter-distribution topologies
(SURVEY.md §2.3, /root/reference/doc/topologies.md):

- **Owner-sharded keys (exact)**: the counter table is sharded by slot over
  the mesh ("shard" axis); the host routes each hit to its owner device
  (the ICI equivalent of Redis-cluster hash-tag sharding, keys.rs:1-13).
  Requests may span devices: admission is all-or-nothing per request, so
  each fixpoint sweep combines per-device hit verdicts with a cross-device
  ``pmin`` over the replicated request vector. Exactness is preserved —
  the fixpoint argument of ops/kernel.py is unchanged, the AND just rides
  ICI.
- **Replicated global counters (psum)**: counters of "global limit"
  namespaces hold a per-device partial count; their effective value is
  ``psum`` of partials (the CRDT read-as-sum of
  distributed/cr_counter_value.rs:38-46 mapped onto ICI collectives).
  Admission uses the psum'd base plus the device-local prefix, so
  over-admission is bounded by one batch per remote device — the same
  bounded-inaccuracy contract the reference documents for its distributed
  and cached-Redis modes (redis_cached.rs:25-41).

Layout: values/expiry are [n_shards, local_capacity+1] with
PartitionSpec("shard", None); hit arrays are [n_shards, H_local] sharded the
same way; request vectors are replicated.

Collective-lean variants
------------------------
Collectives only pay for themselves when a batch actually needs them, and
the always-coupled launch scaled NEGATIVELY with shard count: every batch
paid a psum over the global region plus a pmin over the full replicated
request vector, whether or not any hit was global or any request spanned
shards. The host stages
per-shard hits and KNOWS both facts, so ``sharded_check_and_update``
takes two static flags:

- ``coupled=False`` — no request spans shards: request ids are
  SHARD-LOCAL (``req_ids`` in [0, H_local), ``num_req = H_local``), the
  cross-device ``pmin`` disappears, and ``admitted`` comes back
  ``[n_shards, H_local]`` sharded like the hit arrays (the caller indexes
  it by the request's owner shard). The per-sweep ``segment_min`` also
  shrinks n_shards-fold.
- ``has_global=False`` — no psum-region hit in the batch: the global
  partial sum (and its all-reduce) is skipped entirely.

The default (``coupled=True, has_global=True``) is the fully coupled
program; the four (coupled, has_global) combinations are four compiled
programs, selected per batch by the storage's staging pass. Batch inputs
should be ``jax.device_put`` with :func:`batch_sharding` so each shard
receives only its own rows — handing the jit replicated host arrays makes
XLA materialize every shard's hits on every device and slice them back
out, which is exactly the replication this path exists to avoid (the
HLO regression test in tests/test_sharded.py pins this).
"""

from __future__ import annotations

import base64
import functools
import threading
import time
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax._src.distributed import global_state as _dist_state
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.kernel import check_and_update_core, update_core

__all__ = [
    "ShardedCounterState",
    "ShardedBatchResult",
    "make_sharded_table",
    "make_mesh",
    "make_global_mesh",
    "batch_sharding",
    "sharded_check_and_update",
    "sharded_update",
    "sharded_clear_cells",
    "sharded_drain_top_hits",
    "PodInfo",
    "initialize_pod",
    "pod_info",
    "host_local_to_global",
    "pod_sync",
    "pod_barrier",
    "PodPsumLane",
    "PodMembership",
    "PeerPsumTransport",
    "make_host_mesh",
    "METRIC_FAMILIES",
]

#: metric families this subsystem owns (cross-checked against
#: observability/metrics.py by the analysis registry pass): the
#: lockstep pod psum lane (ISSUE 13) — global-namespace limits decided
#: locally on every host against read-as-sum partials, instead of
#: funneling through one pin host.
METRIC_FAMILIES = (
    "pod_psum_namespaces",
    "pod_psum_decisions",
    "pod_psum_limited",
    "pod_psum_exchanges",
    "pod_psum_cells",
    "pod_psum_remote_slots",
)

_NEVER = jnp.iinfo(jnp.int32).max


def _shard_map(fn, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off — the
    cross-device pmin/psum coupling below is deliberate."""
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


class ShardedCounterState(NamedTuple):
    """``hits`` is the per-slot traffic accumulator (shard-local counts;
    a global counter's traffic lands in each hitting shard's row —
    drains sum it host-side). ``make_sharded_table`` always creates it;
    the sharded kernels below require it present (None is tolerated
    only as a passthrough on rebase/clear for legacy states)."""

    values: jax.Array     # int32[n_shards, L+1] sharded over "shard"
    expiry_ms: jax.Array  # int32[n_shards, L+1] sharded over "shard"
    hits: Optional[jax.Array] = None  # int32[n_shards, L+1]


class ShardedBatchResult(NamedTuple):
    admitted: jax.Array   # bool[R] replicated
    hit_ok: jax.Array     # bool[n_shards, H_local]
    remaining: jax.Array  # int32[n_shards, H_local]
    ttl_ms: jax.Array     # int32[n_shards, H_local]


def make_mesh(devices=None, axis: str = "shard") -> Mesh:
    devices = devices if devices is not None else jax.devices()
    return Mesh(devices, (axis,))


# -- pod-scale (multi-host) plumbing ------------------------------------------
#
# `jax.distributed.initialize()` + a pod-wide Mesh generalize every
# sharded kernel above across hosts (the multihost pjit pattern,
# SNIPPETS [3]): `jax.devices()` becomes the GLOBAL device list, the
# "shard" axis spans processes, and the collective-lean classification
# holds unchanged — a `coupled=False, has_global=False` launch lowers
# with ZERO cross-host collectives on the global mesh exactly as it
# does on ICI (tests/test_pod.py lints the HLO inside a live 2-process
# pod). Each host feeds only its addressable shards:
# `host_local_to_global` lifts host-local [n_local, H] staging rows
# into the global [n_total, H] array without materializing remote rows
# anywhere.


class PodInfo(NamedTuple):
    """The process's place in the pod (degenerate single-process values
    when `jax.distributed` was never initialized)."""

    process_id: int
    num_processes: int
    local_device_count: int
    global_device_count: int

    @property
    def multi_host(self) -> bool:
        return self.num_processes > 1


def initialize_pod(
    coordinator: str, num_processes: int, process_id: int
) -> PodInfo:
    """`jax.distributed.initialize()` with the CPU-pod affordance: on
    the host backend cross-process collectives need the gloo
    implementation (the default 'none' forms the pod but fails the
    first collective with "Multiprocess computations aren't
    implemented"), which is also how the 1/2/4-process bench and the
    2-process parity harness run a pod on one box. Idempotent: a
    second call in an already-initialized process just returns the
    live topology."""
    if _dist_state.coordinator_address is not None:
        return pod_info()
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=int(num_processes),
        process_id=int(process_id),
    )
    return pod_info()


def pod_info() -> PodInfo:
    return PodInfo(
        process_id=jax.process_index(),
        num_processes=jax.process_count(),
        local_device_count=jax.local_device_count(),
        global_device_count=jax.device_count(),
    )


class PodMembership:
    """The pod's membership as a pure control-plane record (ISSUE 18).

    Under per-host meshes nothing about the device plane encodes which
    hosts are in the pod — that fact lives here: (hosts, host_id,
    peers, topology_epoch), flipped by the resize/join coordinator
    under commit and observed by subscribers (the warm standby's
    "am I live yet" signal, metrics). A flip is O(listeners): no jax
    re-form, no process restart — the property the sub-second join
    rides. `jax.process_count()`-style facts keep coming from the
    local runtime (always 1 process in per-host mode); THIS is the
    source of truth for pod-level membership."""

    def __init__(self, hosts: int = 1, host_id: int = 0,
                 peers=(), epoch: int = 0):
        self._lock = threading.Lock()
        self.hosts = int(hosts)
        self.host_id = int(host_id)
        self.peers = tuple(peers)
        self.epoch = int(epoch)
        self._listeners = []

    def subscribe(self, fn) -> None:
        """fn(membership) after every apply(); called outside the
        lock (a listener may read snapshot())."""
        with self._lock:
            self._listeners.append(fn)

    def apply(self, hosts: int, host_id: int, peers=(),
              epoch: Optional[int] = None) -> dict:
        with self._lock:
            self.hosts = int(hosts)
            self.host_id = int(host_id)
            self.peers = tuple(peers)
            self.epoch = (
                self.epoch + 1 if epoch is None else int(epoch)
            )
            listeners = list(self._listeners)
        for fn in listeners:
            try:
                fn(self)
            except Exception:  # a bad listener must not fail a commit
                pass
        return self.snapshot()

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "hosts": self.hosts,
                "host_id": self.host_id,
                "peers": list(self.peers),
                "epoch": self.epoch,
            }

    @property
    def multi_host(self) -> bool:
        return self.hosts > 1


def make_host_mesh(axis: str = "shard") -> Mesh:
    """The PER-HOST mesh (ISSUE 18): this process's devices only, no
    matter how many hosts the pod has. Every pod member's device plane
    is one of these — membership is a pure control-plane fact
    (:class:`PodMembership` / routing.PodTopology) that the resize
    coordinator flips without re-forming any jax runtime, and
    cross-host reads ride the PeerLane (forwarded/bulk decisions) and
    the psum lane instead of cross-host device collectives. Identical
    geometry whether or not `jax.distributed` was ever initialized, so
    a warm standby can form (and compile against) this mesh long
    before it knows which pod it will join."""
    return Mesh(jax.local_devices(), (axis,))


def make_global_mesh(axis: str = "shard") -> Mesh:
    """The pod-wide mesh: every device of every process on one shard
    axis, ordered so each host's addressable devices form a contiguous
    block (global shard `g` belongs to host `g // local_device_count` —
    the contract routing.PodTopology encodes).

    Since ISSUE 18 this is the LEGACY formation: it requires the
    stop-the-world `jax.distributed` pod (fixed num_processes at boot),
    so the serving stack prefers per-host meshes (`make_host_mesh`)
    with the PeerLane for cross-host reads — the jax.distributed bench
    and parity harnesses are its remaining users."""
    procs = sorted(
        {d.process_index for d in jax.devices()}
    )
    ordered = [
        d
        for p in procs
        for d in sorted(
            (d for d in jax.devices() if d.process_index == p),
            key=lambda d: d.id,
        )
    ]
    return Mesh(ordered, (axis,))


def host_local_to_global(mesh: Mesh, arrays, axis: str = "shard"):
    """Lift host-local [n_local, ...] staging arrays into global
    [n_total, ...] arrays on a multi-host mesh (each host contributes
    only its addressable shards — remote rows are never materialized
    here). On a single-process mesh this is the plain sharded
    device_put the storage already performs."""
    sharding = batch_sharding(mesh, axis)
    if len(mesh.devices.flat) == len([
        d for d in mesh.devices.flat if d.process_index == jax.process_index()
    ]):
        return jax.device_put(tuple(arrays), sharding)
    from jax.experimental import multihost_utils

    spec = P(axis, None)
    return tuple(
        multihost_utils.host_local_array_to_global_array(a, mesh, spec)
        for a in arrays
    )


def pod_sync(tag: str = "pod") -> None:
    """DEVICE barrier across the pod's processes (no-op single-
    process): a psum over the global mesh, so it proves the device
    collectives themselves work. Must NOT be held while another thread
    needs the same devices — the CPU client serializes executions per
    device, so a concurrent local launch (e.g. a peer-lane forwarded
    decision) would deadlock against it; those phases use
    :func:`pod_barrier` instead."""
    if jax.process_count() <= 1:
        return
    from jax.experimental import multihost_utils

    multihost_utils.sync_global_devices(tag)


def pod_barrier(tag: str, timeout_ms: int = 120_000) -> None:
    """CONTROL-PLANE barrier across the pod's processes (no-op single-
    process): the coordination-service barrier of the distributed
    runtime — pure RPC, touches no device, so other threads keep
    launching freely while this one waits (the lockstep points of the
    pod drive, where the waiting host's lane thread must stay able to
    serve forwarded decisions)."""
    if jax.process_count() <= 1:
        return
    client = _dist_state.client
    if client is None:  # pragma: no cover - non-distributed fallback
        pod_sync(tag)
        return
    client.wait_at_barrier(tag, timeout_ms)


def batch_sharding(mesh: Mesh, axis: str = "shard") -> NamedSharding:
    """Sharding for [n_shards, H] batch arrays: device_put hit columns
    with this BEFORE the launch so each shard uploads only its own rows
    (a replicated upload costs n_shards x the bytes and leaves XLA to
    slice the local rows back out on device)."""
    return NamedSharding(mesh, P(axis, None))


def make_sharded_table(
    mesh: Mesh, local_capacity: int, axis: str = "shard"
) -> ShardedCounterState:
    n = mesh.shape[axis]
    sharding = NamedSharding(mesh, P(axis, None))
    make = lambda: jax.device_put(
        jnp.zeros((n, local_capacity + 1), jnp.int32), sharding
    )
    return ShardedCounterState(values=make(), expiry_ms=make(), hits=make())


def _local_step(values, expiry, hits, slots, deltas, maxes, windows,
                req_ids, fresh, bucket, is_global, now_ms, num_req, axis,
                global_region, coupled, has_global):
    """Per-device admission over the local shard; runs inside shard_map.

    Delegates to ops/kernel.py's shared ``check_and_update_core`` with two
    cross-device hooks, each compiled in ONLY when the batch needs it
    (module docstring, "Collective-lean variants"):

    - ``vote_combine`` (``coupled`` batches): requests may span devices;
      admission is all-or-nothing, so per-device verdicts AND across the
      mesh via ``pmin`` (devices without hits for a request vote True).
    - ``base_hook`` (``has_global`` batches): global counters occupy the
      same slot (< global_region) on every shard, each holding a
      per-device partial; the effective base is the psum of live partials
      over that compact region (the CRDT read-as-sum riding ICI).
      In-batch remote contributions are not visible until the next batch
      — bounded over-admission, as in the reference's distributed mode.
    """
    base_hook = None
    if has_global:
        live_partial = jnp.where(now_ms < expiry[:global_region],
                                 values[:global_region], 0)
        global_vals = lax.psum(live_partial, axis)
        s_glob = is_global[jnp.argsort(slots, stable=True)]

        def base_hook(v_local, s_slot):
            safe_idx = jnp.minimum(s_slot, global_region - 1)
            return jnp.where(s_glob, global_vals[safe_idx], v_local)

    vote_combine = None
    if coupled:
        def vote_combine(local_vote):
            return lax.pmin(local_vote.astype(jnp.int32), axis).astype(bool)

    return check_and_update_core(
        values, expiry, slots, deltas, maxes, windows, req_ids, fresh,
        bucket, now_ms, num_req, vote_combine=vote_combine,
        base_hook=base_hook, hits=hits,
    )


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "axis", "global_region", "coupled",
                     "has_global"),
    donate_argnums=(1,),
)
def sharded_check_and_update(
    mesh: Mesh,
    state: ShardedCounterState,
    slots: jax.Array,       # int32[n, H_local] owner-local slot per hit
    deltas: jax.Array,      # int32[n, H_local]
    maxes: jax.Array,       # int32[n, H_local]
    windows_ms: jax.Array,  # int32[n, H_local]
    req_ids: jax.Array,     # int32[n, H_local] request ids (see below)
    fresh: jax.Array,       # bool[n, H_local]
    bucket: jax.Array,      # bool[n, H_local] GCRA token-bucket hits
    is_global: jax.Array,   # bool[n, H_local] psum-replicated counter hits
    now_ms: jax.Array,      # int32 scalar
    axis: str = "shard",
    global_region: int = 1024,
    coupled: bool = True,
    has_global: bool = True,
) -> Tuple[ShardedCounterState, ShardedBatchResult]:
    """One fused multi-chip check-and-update step over the sharded table.

    ``coupled`` batches use GLOBAL request ids (< n*H, one id space mesh-
    wide) and return a replicated ``admitted[n*H]``; ``coupled=False``
    batches use SHARD-LOCAL ids (< H, every request's hits on one shard)
    and return ``admitted[n, H]`` sharded like the hit arrays — no
    cross-device collective at all when ``has_global`` is also False.

    Bucket hits are owner-sharded only (the host routes them like any
    exact counter; a TAT cell cannot be a psum global partial, so bucket
    counters in global namespaces stay on the host's exact path)."""
    n, H = slots.shape
    num_req = n * H if coupled else H

    def fn(values, expiry, hits, slots, deltas, maxes, windows, req_ids,
           fresh, bucket, is_global):
        (nv, ne, nh, admitted, ok, remaining, ttl) = _local_step(
            values[0], expiry[0], hits[0], slots[0], deltas[0], maxes[0],
            windows[0], req_ids[0], fresh[0], bucket[0], is_global[0],
            now_ms, num_req, axis, global_region, coupled, has_global,
        )
        if not coupled:
            admitted = admitted[None]  # [1, H]: this shard's verdicts
        return (
            nv[None], ne[None], nh[None], admitted, ok[None],
            remaining[None], ttl[None]
        )

    spec = P(axis, None)
    admitted_spec = P() if coupled else spec
    nv, ne, nh, admitted, ok, remaining, ttl = _shard_map(
        fn,
        mesh=mesh,
        in_specs=(spec,) * 11,
        out_specs=(spec, spec, spec, admitted_spec, spec, spec, spec),
    )(state.values, state.expiry_ms, state.hits, slots, deltas,
      maxes, windows_ms, req_ids, fresh, bucket, is_global)
    return (
        ShardedCounterState(nv, ne, nh),
        ShardedBatchResult(admitted, ok, remaining, ttl),
    )


@functools.partial(
    jax.jit, static_argnames=("mesh", "axis"), donate_argnums=(1,),
)
def sharded_clear_cells(
    mesh: Mesh,
    state: ShardedCounterState,
    slots: jax.Array,  # int32[n, K] per-shard slots to zero (pad: row L)
    axis: str = "shard",
) -> ShardedCounterState:
    """Zero (value, expiry) of per-shard cell lists IN PLACE (donated):
    the slot-release/eviction/delete path. Each shard scatters into its
    own rows — no collective, no full-table host round trip, and no
    un-donated ``.at[].set`` copy of the whole [n, L+1] table (which is
    what this replaces). Padding entries point at the scratch row L,
    which the kernel keeps zero anyway. Zeroing a GLOBAL slot everywhere
    = broadcast the slot list to every row of ``slots``. The hit
    accumulator clears with the cell (a recycled slot must not inherit
    the old occupant's traffic attribution)."""
    spec = P(axis, None)
    if state.hits is None:  # legacy state: no accumulator to clear

        def fn2(values, expiry, slots):
            return (
                values[0].at[slots[0]].set(0)[None],
                expiry[0].at[slots[0]].set(0)[None],
            )

        nv, ne = _shard_map(
            fn2, mesh=mesh, in_specs=(spec,) * 3, out_specs=(spec, spec),
        )(state.values, state.expiry_ms, slots)
        return ShardedCounterState(nv, ne)

    def fn(values, expiry, hits, slots):
        return (
            values[0].at[slots[0]].set(0)[None],
            expiry[0].at[slots[0]].set(0)[None],
            hits[0].at[slots[0]].set(0)[None],
        )

    nv, ne, nh = _shard_map(
        fn, mesh=mesh, in_specs=(spec,) * 4, out_specs=(spec, spec, spec),
    )(state.values, state.expiry_ms, state.hits, slots)
    return ShardedCounterState(nv, ne, nh)


@functools.partial(
    jax.jit, static_argnames=("mesh", "axis"), donate_argnums=(1,),
)
def sharded_update(
    mesh: Mesh,
    state: ShardedCounterState,
    slots: jax.Array,       # int32[n, H_local]
    deltas: jax.Array,      # int32[n, H_local]
    windows_ms: jax.Array,  # int32[n, H_local]
    fresh: jax.Array,       # bool[n, H_local]
    bucket: jax.Array,      # bool[n, H_local]
    now_ms: jax.Array,      # int32 scalar
    axis: str = "shard",
) -> ShardedCounterState:
    """Unconditional batched increments over the sharded table (the
    Report/update and write-behind-authority path): per-shard saturating
    scatter-adds, no admission, no cross-device coupling — a global
    counter's delta simply lands in one shard's partial."""

    def fn(values, expiry, hits, slots, deltas, windows, fresh, bucket):
        nv, ne, nh = update_core(
            values[0], expiry[0], slots[0], deltas[0], windows[0], fresh[0],
            bucket[0], now_ms, hits=hits[0],
        )
        return nv[None], ne[None], nh[None]

    spec = P(axis, None)
    nv, ne, nh = _shard_map(
        fn,
        mesh=mesh,
        in_specs=(spec,) * 8,
        out_specs=(spec, spec, spec),
    )(state.values, state.expiry_ms, state.hits, slots, deltas,
      windows_ms, fresh, bucket)
    return ShardedCounterState(nv, ne, nh)


@functools.partial(
    jax.jit, static_argnames=("mesh", "axis", "k"), donate_argnums=(1,),
)
def sharded_drain_top_hits(
    mesh: Mesh,
    hits: jax.Array,  # int32[n, L+1] the state's accumulator (donated)
    k: int,
    axis: str = "shard",
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Per-shard read-and-reset of the hit accumulator: each shard's K
    hottest local slots, decided on its own device — no collective, and
    only 2*K ints per shard cross the host link. Returns (zeroed_hits,
    counts[n, k] descending per shard, slots[n, k]); count-0 entries
    are filler. The host merges shards (and sums the psum global
    region's per-shard counts) with full slot->counter attribution."""

    def fn(hits):
        counts, slots = lax.top_k(hits[0][:-1], k)
        return jnp.zeros_like(hits), counts[None], slots[None]

    spec = P(axis, None)
    return _shard_map(
        fn, mesh=mesh, in_specs=(spec,), out_specs=(spec, spec, spec),
    )(hits)


# -- lockstep pod psum lane (ISSUE 13) ----------------------------------------
#
# PR 10 pinned every global-limit namespace whole to one deterministic
# host: correct, but it re-creates the hot spot the pod exists to
# remove — 1-1/N of that namespace's traffic pays a peer hop and ONE
# host's device plane carries the whole namespace. The psum lane is the
# read-as-sum CRDT of the single-host global counters (module
# docstring, "Replicated global counters") lifted to host granularity:
# every host keeps an EXACT local partial per counter and decides
# against remote partials folded in by a lockstep exchange, so every
# ingress host answers locally and the namespace stops funneling.
#
# "Lockstep" is load-bearing: the exchange transport is collective
# (every pod host must run round k together, in round order), which is
# what makes the folded base a consistent pod-wide snapshot. The
# default transport rides the coordination-service KV store + barrier
# of the live `jax.distributed` runtime — pure control-plane RPC, no
# device program, because a device-collective exchange would deadlock
# against concurrent local launches exactly like `pod_sync` documents.
# The inaccuracy contract matches the device psum's: between exchange
# rounds a host cannot see deltas admitted remotely, so over-admission
# is bounded by one exchange interval per remote host (the reference's
# cached-Redis bound, redis_cached.rs:25-41).


class PodPsumLane:
    """Host-local exact partials + lockstep-folded remote base for
    global-namespace limits.

    ``configure(limits, global_namespaces)`` claims the namespaces this
    lane can serve (fixed-window only — a GCRA TAT cell cannot be a
    summed partial, the same exclusion the device psum region applies);
    the pod frontend then stops pinning them. The decision surface
    (``check_and_update`` / ``is_rate_limited`` / ``update_counters``)
    is synchronous and lock-cheap: one dict pass over local cells plus
    an int read of the folded remote vector — never an RPC.

    ``exchange()`` runs ONE lockstep round: publish my live partials,
    fold everyone else's. Every pod host must call it the same number
    of times in the same order (the transport is collective); the
    built-in pacing thread keeps hosts in lockstep by construction
    because each round's barrier waits for the slowest host.
    """

    #: remote partials fold into a fixed slot vector so the exchange
    #: payload is bounded; colliding keys MERGE their remote sums —
    #: strictly conservative (a merged base can only under-admit).
    DEFAULT_SLOTS = 2048

    def __init__(
        self,
        hosts: int,
        host_id: int,
        clock=time.time,
        slots: int = DEFAULT_SLOTS,
        cell_cap: int = 1 << 16,
        transport=None,
        barrier_timeout_ms: int = 30_000,
    ):
        from ..core.limiter import CheckResult
        from ..routing import counter_key
        from ..storage.expiring_value import ExpiringValue

        # bound once: the decision surface is registered as a hot
        # module (tracing-safety pass) — per-call `from x import y`
        # inside check_and_update/is_rate_limited would re-run a
        # sys.modules lookup on every psum-served request.
        self._CheckResult = CheckResult
        self._counter_key = counter_key
        self._ExpiringValue = ExpiringValue
        self.hosts = int(hosts)
        self.host_id = int(host_id)
        self._clock = clock
        self._slots = int(slots)
        self._cell_cap = int(cell_cap)
        self._barrier_timeout_ms = int(barrier_timeout_ms)
        #: namespaces (str) this lane serves; read lock-free by the
        #: frontend's `_psum_serves` (set replacement is atomic).
        self.namespaces: frozenset = frozenset()
        self._lock = threading.Lock()
        # counter key tuple -> ExpiringValue (this host's partial),
        # LRU-bounded like the in-memory qualified cache.
        from collections import OrderedDict

        from ..routing import stable_hash

        self._stable_hash = stable_hash
        self._cells: "OrderedDict" = OrderedDict()
        # key -> slot, filled at cell insertion and evicted with the
        # cell: the decision path and every _pack round then never
        # re-run repr+crc32 per key (the staging-pass hot spot
        # routing.RouteMemo documents) — _pack holds the decision lock,
        # so its per-cell cost is latency every psum decision pays.
        self._slot_memo: dict = {}
        # folded remote base (sum of OTHER hosts' live partials at the
        # last exchange round) per slot, with the latest expiry stamp —
        # reads treat an expired slot as 0, mirroring the device psum's
        # live_partial mask.
        self._remote_vals = np.zeros(self._slots, np.int64)
        self._remote_exp = np.zeros(self._slots, np.float64)
        self._transport = transport
        self.rounds = 0
        self.decisions = 0
        self.limited = 0
        self.exchanges = 0
        self._pacer: Optional[threading.Thread] = None
        self._stop = threading.Event()
        # set when the pacing thread dies on a failed exchange; a dead
        # lane must stay unclaimed across limits reloads (configure()
        # would otherwise re-claim namespaces nobody is folding).
        self._pacer_dead = False

    # -- configuration -------------------------------------------------------

    def configure(self, limits, global_namespaces) -> frozenset:
        """Claim the global namespaces every limit of which this lane
        can count (fixed-window policies only). Returns the served set;
        the caller pins the remainder as before."""
        if self._pacer_dead:
            self.namespaces = frozenset()
            return self.namespaces
        by_ns: dict = {}
        for limit in limits:
            by_ns.setdefault(str(limit.namespace), []).append(limit)
        served = frozenset(
            ns for ns in (str(n) for n in global_namespaces)
            if ns in by_ns and all(
                lim.policy == "fixed_window" for lim in by_ns[ns]
            )
        )
        self.namespaces = served
        return served

    # -- internals -----------------------------------------------------------

    def _slot_of(self, key: tuple) -> int:
        s = self._slot_memo.get(key)
        if s is None:
            s = self._stable_hash(key) % self._slots
        return s

    def _cell(self, key: tuple, window_s: int, now: float):
        ev = self._cells.get(key)
        if ev is None:
            # fresh window even on a pure check (the in-memory oracle's
            # in_memory.rs:122-127 semantics)
            ev = self._ExpiringValue(0, now + window_s)
            self._cells[key] = ev
            self._slot_memo[key] = (
                self._stable_hash(key) % self._slots
            )
            while len(self._cells) > self._cell_cap:
                evicted, _ = self._cells.popitem(last=False)
                self._slot_memo.pop(evicted, None)
        else:
            self._cells.move_to_end(key)
        return ev

    def _remote_live(self, key: tuple, now: float) -> int:
        s = self._slot_of(key)
        if now >= self._remote_exp[s]:
            return 0
        return int(self._remote_vals[s])

    # -- the decision surface (sync, called by PodFrontend) ------------------

    def check_and_update(
        self, counters, delta: int, load_counters: bool = False
    ):
        """Check-all-then-update-all over base+partial, the in-memory
        oracle's discipline (never over-admits locally; remote deltas
        since the last round are the bounded blind spot)."""
        CheckResult = self._CheckResult
        counter_key = self._counter_key
        now = self._clock()
        with self._lock:
            self.decisions += 1
            first_limited = None
            to_update = []
            # simple counters first, then qualified — the oracle's
            # first_limited order
            for qualified_pass in (False, True):
                for counter in counters:
                    if counter.is_qualified() is not qualified_pass:
                        continue
                    key = counter_key(counter)
                    ev = self._cell(key, counter.window_seconds, now)
                    value = ev.value_at(now) + self._remote_live(key, now)
                    over = value + delta > counter.max_value
                    if load_counters:
                        remaining = counter.max_value - (value + delta)
                        counter.remaining = max(remaining, 0)
                        counter.expires_in = ev.ttl(now)
                        if first_limited is None and remaining < 0:
                            first_limited = counter.limit.name
                    elif over:
                        self.limited += 1
                        return CheckResult(True, [], counter.limit.name)
                    to_update.append((ev, counter.window_seconds))
            if first_limited is not None:
                self.limited += 1
                return CheckResult(True, list(counters), first_limited)
            for ev, window in to_update:
                ev.update(delta, window, now)
        return CheckResult(False, list(counters) if load_counters else [],
                           None)

    def is_rate_limited(self, counters, delta: int):
        CheckResult = self._CheckResult
        counter_key = self._counter_key
        now = self._clock()
        with self._lock:
            self.decisions += 1
            for counter in counters:
                key = counter_key(counter)
                ev = self._cells.get(key)
                value = (ev.value_at(now) if ev is not None else 0) + \
                    self._remote_live(key, now)
                if value + delta > counter.max_value:
                    self.limited += 1
                    return CheckResult(True, [counter], counter.limit.name)
        return CheckResult(False, [], None)

    def update_counters(self, counters, delta: int) -> None:
        counter_key = self._counter_key
        now = self._clock()
        with self._lock:
            for counter in counters:
                key = counter_key(counter)
                ev = self._cell(key, counter.window_seconds, now)
                ev.update(delta, counter.window_seconds, now)

    # -- the lockstep exchange -----------------------------------------------

    def _pack(self, now: float) -> bytes:
        vals = np.zeros(self._slots, np.int64)
        exps = np.zeros(self._slots, np.float64)
        for key, ev in self._cells.items():
            v = ev.value_at(now)
            if v <= 0:
                continue
            s = self._slot_of(key)
            vals[s] += v
            if ev.expiry > exps[s]:
                exps[s] = ev.expiry
        return vals.tobytes() + exps.tobytes()

    def _unpack(self, payload: bytes):
        n = self._slots
        vals = np.frombuffer(payload[: n * 8], np.int64)
        exps = np.frombuffer(payload[n * 8:], np.float64)
        return vals, exps

    def _kv_transport(self, round_idx: int, payload: bytes):
        """The live-pod default: coordination-service KV + barrier of
        the `jax.distributed` runtime. Pure control-plane RPC — a
        device-collective exchange would deadlock against concurrent
        local launches (the pod_sync caveat)."""
        client = _dist_state.client
        if client is None:
            # A multi-host lane without a coordination client must FAIL
            # the round, not fabricate a healthy one: returning
            # all-None here would keep pod_psum_exchanges advancing
            # while every host folds a permanent-zero remote base —
            # exactly the N-times over-admission the pacer-death
            # unclaim path exists to prevent. Raising routes this
            # through that path (log + unclaim + stop pacing).
            raise RuntimeError(
                "pod psum lane: no jax.distributed coordination client "
                "for the KV exchange"
            )
        client.key_value_set(
            f"psum-lane/{round_idx}/{self.host_id}",
            base64.b64encode(payload).decode(),
        )
        client.wait_at_barrier(
            f"psum-lane-r{round_idx}", self._barrier_timeout_ms
        )
        # Reclaim my previous round's payload: passing round k's barrier
        # means every host completed round k-1 entirely (the lockstep
        # invariant), so the k-1 key can never be read again. Without
        # this the coordination service accrues ~slots*16B per host per
        # round forever (~1.4MB/s on an 8-host pod at the default
        # cadence) until the coordinator OOMs. Best-effort: a client
        # without key_value_delete just leaks like before.
        if round_idx > 0:
            delete = getattr(client, "key_value_delete", None)
            if delete is not None:
                try:
                    delete(f"psum-lane/{round_idx - 1}/{self.host_id}")
                except Exception:
                    pass
        out = []
        for h in range(self.hosts):
            if h == self.host_id:
                out.append(payload)
                continue
            raw = client.blocking_key_value_get(
                f"psum-lane/{round_idx}/{h}", self._barrier_timeout_ms
            )
            out.append(base64.b64decode(raw))
        return out

    def exchange(self) -> int:
        """One lockstep exchange round; returns the round count. Every
        pod host MUST call this the same number of times, in order (the
        transport is collective — the round's barrier paces all hosts
        to the slowest). Single-host pods fold nothing and stay
        exact."""
        now = self._clock()
        with self._lock:
            payload = self._pack(now)
            round_idx = self.rounds
        transport = self._transport or self._kv_transport
        payloads = transport(round_idx, payload)
        rv = np.zeros(self._slots, np.int64)
        re_ = np.zeros(self._slots, np.float64)
        for h, p in enumerate(payloads):
            if h == self.host_id or p is None:
                continue
            pv, pe = self._unpack(p)
            rv += pv
            np.maximum(re_, pe, out=re_)
        with self._lock:
            self._remote_vals = rv
            self._remote_exp = re_
            self.rounds = round_idx + 1
            self.exchanges += 1
        return self.rounds

    def start(self, interval_s: float = 0.25) -> None:
        """Pace lockstep rounds on a daemon thread: sleep, then
        exchange — the per-round barrier keeps every host's thread on
        the same round index (the fastest host waits). A host that
        stops responding times every peer's barrier out; each pacer
        then UNCLAIMS its namespaces before exiting, so the frontend's
        per-decision `_psum_serves` check reverts them to the pinned
        (exact, single-owner) path — a dead exchange must not leave N
        hosts each admitting the full limit on a base going stale."""
        if self._pacer is not None or self.hosts <= 1:
            return

        def run():
            while not self._stop.wait(interval_s):
                try:
                    self.exchange()
                except Exception:
                    if not self._stop.is_set():
                        import logging

                        logging.getLogger("limitador").warning(
                            "pod psum lane: exchange failed at round "
                            f"{self.rounds} (barrier timeout or peer "
                            "loss); unclaiming "
                            f"{len(self.namespaces)} namespaces — "
                            "they revert to the pinned path",
                            exc_info=True,
                        )
                    self._pacer_dead = True
                    self.namespaces = frozenset()
                    return

        self._pacer = threading.Thread(
            target=run, name="pod-psum-lane", daemon=True
        )
        self._pacer.start()

    def close(self) -> None:
        self._stop.set()

    # -- telemetry -----------------------------------------------------------

    def stats(self) -> dict:
        with self._lock:
            live_remote = int(
                np.count_nonzero(
                    self._remote_vals
                    * (self._remote_exp > self._clock())
                )
            )
            return {
                "pod_psum_namespaces": len(self.namespaces),
                "pod_psum_decisions": self.decisions,
                "pod_psum_limited": self.limited,
                "pod_psum_exchanges": self.exchanges,
                "pod_psum_cells": len(self._cells),
                "pod_psum_remote_slots": live_remote,
            }


class PeerPsumTransport:
    """PeerLane-backed exchange for :class:`PodPsumLane` (ISSUE 18).

    Under per-host meshes there is no `jax.distributed` coordination
    client, so the psum lane's KV+barrier transport is unavailable —
    this transport replaces it with a push over the pod's gRPC peer
    lane. The contract loosens from barrier-lockstep to PACED: each
    host publishes its newest partials every round (``send(host_id,
    payload)`` — peering wires it to a ``kind:"psum_share"`` unary)
    and folds the newest payload it has RECEIVED from each peer
    (``receive()`` is the lane handler's delivery). A missing peer
    contributes None (the fold skips it), so a dead host costs
    staleness bounded by the pacing interval instead of stalling a
    pod-wide barrier.

    The pacer-death safety contract carries over: a peer whose
    payloads stop arriving ages out after ``stale_after_s`` (its
    partials fold as zero — bounded over-admission, the same blind
    spot a slow barrier round had), and when EVERY peer has been
    silent for ``dead_after_rounds`` consecutive rounds the transport
    raises, routing the lane through its unclaim path — N hosts must
    not each admit the full limit against a permanently-zero base."""

    def __init__(self, host_id: int, send, hosts: int = 1,
                 stale_after_s: float = 2.0,
                 dead_after_rounds: int = 8, clock=time.time):
        self.host_id = int(host_id)
        self.hosts = int(hosts)
        self._send = send
        self._stale_after_s = float(stale_after_s)
        self._dead_after_rounds = int(dead_after_rounds)
        self._clock = clock
        self._lock = threading.Lock()
        self._rx: dict = {}  # host -> (recv_monotonic, payload)
        self._silent_rounds = 0
        self.published = 0
        self.send_errors = 0

    def attach(self, hosts: int, host_id: Optional[int] = None) -> None:
        """Membership flip (resize/join commit): widen or shrink the
        fold without dropping already-received payloads."""
        with self._lock:
            self.hosts = int(hosts)
            if host_id is not None:
                self.host_id = int(host_id)
            self._silent_rounds = 0

    def receive(self, host: int, payload: bytes) -> None:
        """Lane delivery: a peer's published partials."""
        with self._lock:
            self._rx[int(host)] = (self._clock(), payload)

    def __call__(self, round_idx: int, payload: bytes):
        with self._lock:
            hosts, host_id = self.hosts, self.host_id
        for h in range(hosts):
            if h == host_id:
                continue
            try:
                self._send(h, payload)
            except Exception:
                self.send_errors += 1
        self.published += 1
        now = self._clock()
        out = []
        fresh_peers = 0
        with self._lock:
            for h in range(hosts):
                if h == host_id:
                    out.append(payload)
                    continue
                got = self._rx.get(h)
                if got is None or now - got[0] > self._stale_after_s:
                    out.append(None)
                else:
                    out.append(got[1])
                    fresh_peers += 1
            if hosts > 1 and fresh_peers == 0:
                self._silent_rounds += 1
            else:
                self._silent_rounds = 0
            if (hosts > 1
                    and self._silent_rounds >= self._dead_after_rounds):
                raise RuntimeError(
                    "peer psum transport: every peer silent for "
                    f"{self._silent_rounds} rounds; unclaiming"
                )
        return out
