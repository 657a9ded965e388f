"""Zero-Python hot lane: deterministic fuzz parity with the pure-Python
lane, and the coherence contracts the C plan mirror must honor.

The corpus covers the wire shapes the hot lane has to route correctly:
multi-descriptor requests (exact path), unknown proto fields, long
values, CEL-gated limits, a token-bucket + fixed-window mix, empty
domains, empty-limits namespaces and hits_addend variation. For every
seed the suite runs the SAME blob sequence through two pipelines —
hot lane forced on vs forced off — over independent storages with a
frozen clock, and asserts byte-identical responses AND identical final
counter state (the check-all-then-update-all admission must not drift
by one hit).

The reload-race tests pin the mirror's epoch contract: a limits bump
mid-flight orphans every mirrored plan before any lookup under the new
epoch, and a stale-epoch put is discarded.
"""

import numpy as np
import pytest

from limitador_tpu import Limit, native
from limitador_tpu.server.proto import rls_pb2
from limitador_tpu.tpu import AsyncTpuStorage, TpuStorage
from limitador_tpu.tpu.pipeline import CompiledTpuLimiter

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native hostpath unavailable"
)

D = "descriptors[0]"
FROZEN_NOW = 1_700_000_000.0


def _limits():
    return [
        Limit("api", 3, 60, [f"{D}.m == 'GET'"], [f"{D}.u"], name="per-get"),
        Limit("api", 7, 120, [], [f"{D}.u"], name="per-user"),
        # CEL-gated on a second descriptor key (vectorized equality)
        Limit("api", 5, 60, [f"{D}.tier == 'pro'"], [f"{D}.tier"],
              name="cel-gated"),
        Limit("bucket", 4, 60, [], [f"{D}.u"], name="tb",
              policy="token_bucket"),
        Limit("mixed", 2, 30, [f"{D}.m == 'GET'"], [f"{D}.u"], name="fw"),
        Limit("mixed", 6, 60, [], [f"{D}.u"], name="tb2",
              policy="token_bucket"),
        # empty-variables limit: a single shared counter
        Limit("shared", 10, 60, [], [], name="global"),
        # non-vectorizable predicate: the whole namespace routes exact
        # (slow rows stay None on BOTH lanes)
        Limit("slowns", 2, 60, [f"{D}.u.startsWith('u')"], [f"{D}.u"],
              name="regexy"),
    ]


def _build(hot: bool):
    limiter = CompiledTpuLimiter(
        AsyncTpuStorage(
            TpuStorage(capacity=1 << 12, clock=lambda: FROZEN_NOW),
            max_delay=0.001,
        )
    )
    for limit in _limits():
        limiter.add_limit(limit)
    from limitador_tpu.tpu.native_pipeline import NativeRlsPipeline

    pipeline = NativeRlsPipeline(limiter, None, max_delay=0.001,
                                 hot_lane=hot)
    if hot:
        assert pipeline.hot_lane_active, "hot lane requested but inactive"
    return pipeline, limiter


def _corpus(seed: int, n: int = 400):
    """Deterministic blob corpus: every wire shape the lane must route."""
    rng = np.random.default_rng(seed)
    blobs = []
    domains = ["api", "bucket", "mixed", "shared", "nolimits", "",
               "slowns"]
    for _ in range(n):
        roll = rng.integers(0, 10)
        req = rls_pb2.RateLimitRequest(
            domain=str(domains[int(rng.integers(0, len(domains)))])
        )
        if roll >= 8:
            req.hits_addend = int(rng.integers(0, 4))
        n_desc = 2 if roll == 7 else 1  # multi-descriptor -> exact path
        for _d in range(n_desc):
            d = req.descriptors.add()
            e = d.entries.add()
            e.key = "m"
            e.value = "GET" if rng.integers(0, 3) else "POST"
            e = d.entries.add()
            e.key = "u"
            if roll == 6:  # long value
                e.value = "u-" + "x" * int(rng.integers(100, 400))
            else:
                e.value = f"user-{int(rng.integers(0, 12))}"
            if rng.integers(0, 2):
                e = d.entries.add()
                e.key = "tier"
                e.value = str(
                    ["pro", "plus", "free"][int(rng.integers(0, 3))]
                )
        blob = req.SerializeToString()
        if roll == 5:
            # unknown field (tag 15, varint): parsers must skip it and
            # both lanes must cache/decide the EXACT bytes
            blob += b"\x78\x2a"
        blobs.append(blob)
        if roll == 9 and blobs:
            # byte-identical repeat of an earlier blob: the hot lane's
            # bread and butter
            blobs.append(blobs[int(rng.integers(0, len(blobs)))])
    return blobs


def _counter_state(limiter):
    """Comparable final counter state across both pipelines."""
    import asyncio

    async def collect():
        out = set()
        for ns in ("api", "bucket", "mixed", "shared"):
            for counter in await limiter.get_counters(ns):
                out.add((
                    counter.namespace,
                    counter.limit.name,
                    tuple(sorted((counter.set_variables or {}).items())),
                    counter.remaining,
                    round(counter.expires_in or 0.0, 3),
                ))
        return out

    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(collect())
    finally:
        loop.close()


def _norm(results, pipeline):
    """decide_many rows: bytes, None (slow/exact path) or the
    STORAGE_ERROR sentinel — normalize the sentinel for comparison."""
    return [
        "STORAGE_ERROR" if r is pipeline.STORAGE_ERROR else r
        for r in results
    ]


def _decide_cached(pipeline, batch):
    """Drive one batch through the cached begin/finish split — the C
    hot lane on a hot pipeline, the pure-Python plan-cache lane on a
    lane-off pipeline. Both share the cached-lane launch discipline
    (cached rows launch before miss rows), so parity here is exact
    byte-for-byte, ordering included."""
    with pipeline._native_lock:
        results, _slow, pendings, _foreign = pipeline._begin_batch_locked(
            list(batch), use_cache=True
        )
    for pending in pendings:
        pipeline._finish_namespace(pending, results)
    return results


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_fuzz_corpus_byte_identical_and_state_identical(seed):
    """C++ hot lane vs the pure-Python cached lane, batched: both sides
    run the same two-lane launch discipline, so responses must be
    byte-identical per row and the final counter state identical."""
    blobs = _corpus(seed)
    p_on, lim_on = _build(True)
    p_off, lim_off = _build(False)
    # Two passes: the second one serves from the mirror on the hot side
    # (fresh counters state keeps accumulating on both).
    for _pass in range(2):
        for ofs in range(0, len(blobs), 64):
            batch = blobs[ofs:ofs + 64]
            out_on = _norm(_decide_cached(p_on, batch), p_on)
            out_off = _norm(_decide_cached(p_off, batch), p_off)
            assert out_on == out_off, f"batch at {ofs}"
    assert _counter_state(lim_on) == _counter_state(lim_off)
    # the lane actually served (this is a parity test, not a skip test)
    stats = p_on.lane_stats()
    assert stats["hits"] > 0, stats
    assert stats["staged_hits"] > 0, stats


@pytest.mark.parametrize("seed", [4, 5])
def test_fuzz_corpus_matches_no_cache_lane_serially(seed):
    """C++ hot lane vs the cache-free parse lane, one row per batch:
    with no intra-batch lane mixing, the hot lane's decisions must match
    the simplest exact lane absolutely (same responses, same final
    counters). This pins correctness; the batched test above pins the
    shared cached-lane ordering discipline."""
    blobs = _corpus(seed, n=150)
    p_on, lim_on = _build(True)
    p_off, lim_off = _build(False)
    for _pass in range(2):
        for b in blobs:
            out_on = _norm(p_on.decide_many([b], chunk=8), p_on)
            with p_off._native_lock:
                results, _slow, pendings, _foreign = p_off._begin_batch_locked(
                    [b], use_cache=False
                )
            for pending in pendings:
                p_off._finish_namespace(pending, results)
            assert out_on == _norm(results, p_off)
    assert _counter_state(lim_on) == _counter_state(lim_off)
    assert p_on.lane_stats()["hits"] > 0


def test_repeat_descriptors_all_outcomes_through_the_lane():
    """OK, OVER, UNKNOWN and empty-namespace rows all flow through the
    coded lane with byte parity once plans are mirrored."""
    p_on, _ = _build(True)
    p_off, _ = _build(False)

    def blob(domain, u):
        req = rls_pb2.RateLimitRequest(domain=domain)
        d = req.descriptors.add()
        e = d.entries.add()
        e.key, e.value = "m", "GET"
        e = d.entries.add()
        e.key, e.value = "u", u
        return req.SerializeToString()

    seq = (
        [blob("api", "a")] * 6       # 3 OK then OVER (per-get limit 3)
        + [blob("", "x")] * 2        # UNKNOWN
        + [blob("nolimits", "y")] * 2  # empty-namespace OK
    )
    out_on = [p_on.decide_many([b], chunk=8)[0] for b in seq]
    out_off = [p_off.decide_many([b], chunk=8)[0] for b in seq]
    assert out_on == out_off
    assert out_on[:3] == [p_on.OK_BLOB] * 3
    assert out_on[3:6] == [p_on.OVER_BLOB] * 3
    assert out_on[6:8] == [p_on.UNKNOWN_BLOB] * 2
    assert out_on[8:] == [p_on.OK_BLOB] * 2
    assert p_on.lane_stats()["hits"] > 0


def test_mid_flight_limits_reload_honors_epoch():
    """A limits change between batches must orphan every mirrored plan:
    the next decision reflects the NEW limits, never a cached stale
    template."""
    p, limiter = _build(True)

    req = rls_pb2.RateLimitRequest(domain="api")
    d = req.descriptors.add()
    e = d.entries.add()
    e.key, e.value = "m", "GET"
    e = d.entries.add()
    e.key, e.value = "u", "race"
    blob = req.SerializeToString()

    assert p.decide_many([blob], chunk=8)[0] == p.OK_BLOB
    assert p.decide_many([blob], chunk=8)[0] == p.OK_BLOB  # mirror hit
    before = p.lane_stats()
    assert before["hits"] >= 1 and before["plans"] >= 1
    # reload: the same limit tightens to 0 -> everything OVER
    limiter.update_limit(
        Limit("api", 0, 60, [f"{D}.m == 'GET'"], [f"{D}.u"],
              name="per-get")
    )
    p.invalidate()
    assert p.decide_many([blob], chunk=8)[0] == p.OVER_BLOB
    after = p.lane_stats()
    assert after["epoch"] > before["epoch"]


def test_kernel_columns_do_not_alias_the_staging_buffers():
    """A launch does not consume its host arguments before it returns
    (the CPU backend aliases an aligned numpy buffer; an accelerator
    may read it until the transfer completes), and the next begin
    rewrites the staging buffers: the columns a launch takes must be
    owned copies,
    or a lagging launch decides the NEXT batch's rows. ``fresh`` is
    never written and may stay a view."""
    p, _ = _build(True)
    lane = p._hot_lane
    cols = lane.kernel_columns(64)
    staging = (lane.slots, lane.deltas, lane.maxes, lane.windows,
               lane.req, None, lane.bucket)
    assert len(cols) == len(staging)
    for col, buf in zip(cols, staging):
        assert col.shape == (64,)
        if buf is not None:
            assert not np.shares_memory(col, buf)


def test_stale_epoch_put_is_discarded():
    """The put-side half of the race: a plan derived under epoch E must
    not enter the mirror once the epoch moved past E (the derivation
    raced a reload on another thread)."""
    p, _ = _build(True)
    lane = p._hot_lane
    cache = p.plan_cache
    stale_epoch = cache.epoch
    cache.bump_epoch()
    lane.sync_epoch(cache.epoch)
    lane.plan_put(b"stale-blob", stale_epoch, native.LANE_OK, -1, 1, 1)
    assert p.hp.plan_count() == 0
    # a current-epoch put lands
    lane.plan_put(b"fresh-blob", cache.epoch, native.LANE_OK, -1, 1, 1)
    assert p.hp.plan_count() == 1


def test_slot_release_invalidates_mirrored_plan_even_after_python_evict():
    """The mirror must drop a plan pinning a released slot even when the
    PYTHON cache already evicted that plan (its reverse index alone
    proves nothing about the mirror)."""
    p, _ = _build(True)
    lane = p._hot_lane

    req = rls_pb2.RateLimitRequest(domain="api")
    d = req.descriptors.add()
    e = d.entries.add()
    e.key, e.value = "m", "GET"
    e = d.entries.add()
    e.key, e.value = "u", "evictee"
    blob = req.SerializeToString()
    assert p.decide_many([blob], chunk=8)[0] == p.OK_BLOB
    assert p.hp.plan_count() >= 1
    # drop the plan from the python cache only (simulates LRU eviction)
    p.plan_cache._entries.pop(blob, None)
    plans_before = p.hp.plan_count()
    # release every slot the storage holds: the mirror must invalidate
    # through the unconditional forward even though the python cache no
    # longer indexes the blob
    storage = p.storage
    with storage._lock:
        for slot, (key, counter) in list(storage._table.info.items()):
            storage._table.release(slot, key, counter.is_qualified())
    assert p.hp.plan_count() < plans_before
    lane_stats = lane.stats()
    assert lane_stats["invalidations"] >= 1


def test_hot_lane_off_pipeline_has_no_mirror():
    p, _ = _build(False)
    assert not p.hot_lane_active
    assert p.lane_stats() == {}


@pytest.mark.parametrize("seed", [6, 7])
def test_lease_corpus_conservation_and_settle(seed):
    """The lease tier over the full fuzz corpus (every wire shape:
    multi-descriptor, unknown fields, CEL gating, token buckets, empty
    domains, hits_addend variation): every granted token must be
    consumed, returned or outstanding at all times — and a forced
    settle (reload epoch bump + expiry sweep) drives outstanding to
    zero with nothing stranded. Token conservation is the corpus-wide
    face of the over-admission bound (the per-counter form is pinned in
    test_lease.py)."""
    if not native.lease_available():
        pytest.skip("native lease lane unavailable")
    from limitador_tpu.lease import LeaseConfig

    clock = {"now": FROZEN_NOW}
    limiter = CompiledTpuLimiter(
        AsyncTpuStorage(
            TpuStorage(capacity=1 << 12, clock=lambda: clock["now"]),
            max_delay=0.001,
        )
    )
    for limit in _limits():
        limiter.add_limit(limit)
    from limitador_tpu.tpu.native_pipeline import NativeRlsPipeline

    pipeline = NativeRlsPipeline(limiter, None, max_delay=0.001,
                                 hot_lane=True)
    broker = pipeline.attach_lease(
        LeaseConfig(max_tokens=8, hot_threshold=2, ttl_s=30.0),
        autostart=False,
    )
    broker._clock = lambda: clock["now"]

    blobs = _corpus(seed)
    for _pass in range(3):
        for ofs in range(0, len(blobs), 64):
            _decide_cached(pipeline, blobs[ofs:ofs + 64])
            broker.refresh()
            stats = broker.stats()
            assert stats["lease_granted_tokens"] == (
                stats["lease_admissions"]
                + stats["lease_returned_tokens"]
                + stats["lease_outstanding_tokens"]
            ), stats
        # roll every window: the corpus limits are tiny, so headroom
        # (and with it grantability) refreshes between passes — this
        # also drives leases ACROSS window rolls under the full corpus
        clock["now"] += 121.0
    assert broker.stats()["lease_admissions"] > 0, "leases never engaged"
    # forced settle: reload bump strands every live balance onto the
    # ring; one begin syncs the epoch, the expiry sweep catches the rest
    pipeline.invalidate()
    _decide_cached(pipeline, blobs[:8])
    clock["now"] += 10_000.0
    broker.refresh()
    stats = broker.stats()
    assert stats["lease_outstanding_tokens"] == 0
    assert stats["lease_granted_tokens"] == (
        stats["lease_admissions"] + stats["lease_returned_tokens"]
    ), stats


# -- pod-mode shard-aware hot lane (ISSUE 13) ---------------------------------


def _free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _build_pod_pair(resilient: bool = False):
    """Two hot pipelines behind PodFrontends + real PeerLanes on
    localhost — the server's pod wiring shape: each pipeline wraps its
    host's frontend (the exact path keeps routed semantics) and
    ``attach_pipeline`` arms the C ownership split + bulk lane.
    ``resilient=True`` opts into the PR 11 degraded-owner machinery
    (the server default); False pins the legacy fail-fast posture the
    parity drives want."""
    pytest.importorskip("grpc")
    import asyncio

    from limitador_tpu.routing import PodRouter, PodTopology
    from limitador_tpu.server.peering import (
        PeerLane,
        PodFrontend,
        PodResilience,
    )
    from limitador_tpu.tpu.native_pipeline import NativeRlsPipeline

    if not native.pod_available():
        pytest.skip("native pod ownership mirror unavailable")
    resilience = PodResilience(probe_interval_s=0.1) if resilient else None
    ports = [_free_port(), _free_port()]
    pipelines, frontends, lanes, limiters = [], [], [], []
    for host in range(2):
        limiter = CompiledTpuLimiter(
            AsyncTpuStorage(
                TpuStorage(capacity=1 << 12, clock=lambda: FROZEN_NOW),
                max_delay=0.001,
            )
        )
        lane = PeerLane(
            host,
            f"127.0.0.1:{ports[host]}",
            {
                other: f"127.0.0.1:{ports[other]}"
                for other in range(2)
                if other != host
            },
            None,
            resilience=resilience,
        )
        lane.start()
        router = PodRouter(
            PodTopology(hosts=2, host_id=host, shards_per_host=1)
        )
        frontend = PodFrontend(limiter, router, lane)
        asyncio.run(frontend.configure_with(_limits()))
        pipeline = NativeRlsPipeline(
            frontend, None, max_delay=0.001, hot_lane=True
        )
        assert pipeline.hot_lane_active
        frontend.attach_pipeline(pipeline)
        pipelines.append(pipeline)
        frontends.append(frontend)
        lanes.append(lane)
        limiters.append(limiter)
    return pipelines, frontends, lanes, limiters


@pytest.mark.parametrize("seed", [21, 22])
def test_pod_hot_lane_fuzz_matches_single_process_oracle(seed):
    """THE pod byte-parity drive (ISSUE 13): the full fuzz corpus
    arrives round-robin at a 2-host pod whose native hot lanes are
    shard-aware — locally-owned rows stage zero-Python, foreign-owned
    rows bulk-forward one RPC per (owner, flush), pinned namespaces
    funnel whole — and every response is byte-identical to a
    single-process hot pipeline on the same sequence, with the UNION of
    both hosts' final counter state identical to the oracle's (each
    counter lives on exactly one host)."""
    blobs = _corpus(seed, n=260)
    pipelines, frontends, lanes, limiters = _build_pod_pair()
    p_oracle, lim_oracle = _build(True)
    try:
        for _pass in range(2):  # pass 2 rides the mirrored owner stamps
            for step, ofs in enumerate(range(0, len(blobs), 48)):
                batch = blobs[ofs:ofs + 48]
                arrival = pipelines[step % 2]  # round-robin ingress
                out_pod = _norm(
                    arrival.decide_many(batch, chunk=16), arrival
                )
                out_oracle = _norm(
                    p_oracle.decide_many(batch, chunk=16), p_oracle
                )
                assert out_pod == out_oracle, f"pass {_pass} batch {ofs}"
        state_pod = _counter_state(limiters[0]) | _counter_state(
            limiters[1]
        )
        assert state_pod == _counter_state(lim_oracle)
        # no counter is double-homed
        assert not (
            _counter_state(limiters[0]) & _counter_state(limiters[1])
        )
        # the split really engaged on BOTH sides of the lane
        foreign = sum(
            p.lane_stats()["foreign"] for p in pipelines
        )
        assert foreign > 0, "no foreign rows classified"
        bulk_batches = sum(lane.bulk_forwards for lane in lanes)
        bulk_rows = sum(lane.bulk_forward_rows for lane in lanes)
        served_rows = sum(lane.bulk_served_rows for lane in lanes)
        assert bulk_batches > 0 and bulk_rows >= bulk_batches
        assert served_rows == bulk_rows  # every forwarded row served
        # bulk amortization: strictly fewer RPCs than rows forwarded
        # (the 1-RPC-per-decision floor this lane exists to beat) —
        # the corpus repeats descriptors, so flushes group rows
        assert bulk_batches < bulk_rows
        stats = pipelines[0].pod_stats()
        assert stats["pod_hot_foreign_rows"] + stats[
            "pod_hot_local_rows"] > 0
    finally:
        for lane in lanes:
            lane.stop()


def test_pod_hot_lane_degraded_owner_falls_back_exact():
    """A dead owner host must not fail (or mis-decide) its foreign
    rows: the bulk forward fails, every row falls back to the exact
    per-request path whose limiter is the pod frontend — the PR 11
    degraded stand-in decides exactly, so the sequence still matches
    the single-process oracle byte for byte."""
    import asyncio
    import threading

    from limitador_tpu.routing import PodRouter
    from limitador_tpu.server.proto import rls_pb2

    pipelines, frontends, lanes, limiters = _build_pod_pair(
        resilient=True
    )
    p_oracle, _ = _build(True)

    def blob(u):
        req = rls_pb2.RateLimitRequest(domain="api")
        d = req.descriptors.add()
        e = d.entries.add()
        e.key, e.value = "m", "GET"
        e = d.entries.add()
        e.key, e.value = "u", u
        return req.SerializeToString()

    # "api" is multi-limit -> pinned whole to one deterministic host;
    # drive from the OTHER host with the pin host's lane dead.
    pin = PodRouter.pin_host("api", 2)
    origin = pipelines[1 - pin]
    try:
        lanes[pin].stop()  # the owner is gone mid-serve
        seq = [blob("degraded-user")] * 6

        async def drive():
            outs = []
            for b in seq:
                outs.append(await origin.submit_async(b))
            return outs

        loop = asyncio.new_event_loop()
        t = threading.Thread(target=loop.run_forever, daemon=True)
        t.start()
        try:
            outs = asyncio.run_coroutine_threadsafe(
                drive(), loop
            ).result(60)
        finally:
            loop.call_soon_threadsafe(loop.stop)
            t.join(5)
        want = [p_oracle.decide_many([b], chunk=8)[0] for b in seq]
        assert outs == want  # 3 OK then 3 OVER (per-get limit 3)
        # the decisions came from the degraded machinery, not the lane
        stats = frontends[1 - pin].library_stats()
        assert stats["pod_failover_degraded_decisions"] >= 1, stats
    finally:
        for lane in lanes:
            lane.stop()


def test_pod_psum_served_namespace_takes_exact_path():
    """A psum-claimed global namespace must NOT ride the columnar hot
    lane (the device table would double-count what the psum lane
    serves): its rows answer None from the engine path — the exact
    per-request fallback owns them — while other namespaces keep the
    fast path."""
    import asyncio

    from limitador_tpu.parallel.mesh import PodPsumLane
    from limitador_tpu.server.proto import rls_pb2

    pipelines, frontends, lanes, limiters = _build_pod_pair()
    try:
        for host, f in enumerate(frontends):
            lane = PodPsumLane(2, host, clock=lambda: FROZEN_NOW)
            f.attach_psum_lane(lane)
            asyncio.run(f.configure_with(_limits()))
        # re-derive namespace plans under the new claim
        for p in pipelines:
            p.invalidate()
        # "shared" (fixed-window, empty vars) becomes psum-served once
        # it is global; claim it on both hosts
        for f in frontends:
            f._global_ns = {"shared"}
            asyncio.run(f.configure_with(_limits()))
        for p in pipelines:
            p.invalidate()

        def blob(domain, u):
            req = rls_pb2.RateLimitRequest(domain=domain)
            d = req.descriptors.add()
            e = d.entries.add()
            e.key, e.value = "m", "GET"
            e = d.entries.add()
            e.key, e.value = "u", u
            return req.SerializeToString()

        out = pipelines[0].decide_many(
            [blob("shared", "s1"), blob("api", "a1")], chunk=8
        )
        assert out[0] is None  # psum-served: exact path owns it
        assert out[1] is not None  # other namespaces keep the lane
    finally:
        for lane in lanes:
            lane.stop()


def test_native_partition_matches_numpy():
    """The C partition pass (hp_partition_positions) must produce the
    exact (counts, pos) the numpy argsort path does — it rides every
    MicroBatcher flush on sharded storage above the size threshold."""
    counts_pos = native.partition_positions(
        np.asarray([1, 0, 1, 2, 0, 1], np.int32), 4
    )
    if counts_pos is None:
        pytest.skip("hostpath not loaded")
    rng = np.random.default_rng(11)
    for n, n_groups in ((1, 1), (7, 3), (4096, 8), (50_000, 13)):
        gids = rng.integers(0, n_groups, n).astype(np.int32)
        n_counts, n_pos = native.partition_positions(gids, n_groups)
        counts = np.bincount(gids, minlength=n_groups)
        order = np.argsort(gids, kind="stable")
        starts = np.concatenate(([0], np.cumsum(counts)[:-1]))
        pos = np.empty(n, np.int64)
        pos[order] = np.arange(n, dtype=np.int64) - np.repeat(
            starts, counts
        )
        assert np.array_equal(n_counts, counts)
        assert np.array_equal(n_pos, pos)
