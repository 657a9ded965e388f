"""Tenant usage observatory (ISSUE 8): the per-slot hit accumulator,
the heavy-hitter drain, attribution, the native leased merge, and the
unified control-signal bus.

The oracle discipline: an independent spy counts every real (non-
scratch) hit row the storage actually stages per slot, mapped to
counter identity at stage time. In ``--lease-mode off`` the observatory
must reproduce those counts EXACTLY (every kernel hit — admitted or
rejected — counts once; padding, credits and drains don't). With
leasing on, the merged counts stay within the leased-token bounds
(grant debits ride the check kernel — one accumulator count per slot
per grant — and leased consumption merges in from the native counts).
"""

import threading
import time
from collections import Counter as TallyCounter

import numpy as np
import pytest

from limitador_tpu import Context, Limit, RateLimiter, native
from limitador_tpu.core.counter import Counter
from limitador_tpu.observability.signals import (
    ControlSignals,
    SignalBus,
    _PHASES,
    _PRIORITIES,
)
from limitador_tpu.observability.usage import TenantUsageObservatory
from limitador_tpu.ops import kernel as K
from limitador_tpu.server.proto import rls_pb2
from limitador_tpu.tpu import AsyncTpuStorage, TpuStorage
from limitador_tpu.tpu.pipeline import CompiledTpuLimiter
from limitador_tpu.tpu.storage import _Request

D = "descriptors[0]"


# -- kernel level ------------------------------------------------------------


def _hits(state):
    return np.asarray(state.hits)


def test_kernel_accumulates_every_hit_admitted_or_not():
    state = K.make_table(8)
    # slot 1: three hits across two requests (one will be rejected);
    # slot 3: one hit; padding rows on the scratch slot.
    slots = np.asarray([1, 1, 3, 1, 8, 8, 8, 8], np.int32)
    deltas = np.asarray([2, 2, 1, 2, 0, 0, 0, 0], np.int32)
    maxes = np.asarray([4, 4, 10, 4] + [2**31 - 1] * 4, np.int32)
    windows = np.asarray([60_000] * 4 + [0] * 4, np.int32)
    req = np.asarray([0, 1, 2, 3, 7, 7, 7, 7], np.int32)
    fresh = np.zeros(8, bool)
    bucket = np.zeros(8, bool)
    state, result = K.check_and_update_batch(
        state, slots, deltas, maxes, windows, req, fresh, bucket,
        np.int32(1000),
    )
    admitted = np.asarray(result.admitted)
    assert admitted[0] and admitted[1] and not admitted[3]  # 2+2 then reject
    hits = _hits(state)
    assert hits[1] == 3  # rejected hit counts too: it IS the traffic
    assert hits[3] == 1
    assert hits[-1] == 0  # scratch stays inert
    assert hits[[0, 2, 4, 5, 6, 7]].sum() == 0


def test_kernel_fresh_slot_resets_old_occupants_counts():
    state = K.make_table(8)
    slots = np.asarray([2, 8, 8, 8, 8, 8, 8, 8], np.int32)
    deltas = np.asarray([1] + [0] * 7, np.int32)
    maxes = np.asarray([10] + [2**31 - 1] * 7, np.int32)
    windows = np.asarray([60_000] + [0] * 7, np.int32)
    req = np.asarray([0, 7, 7, 7, 7, 7, 7, 7], np.int32)
    bucket = np.zeros(8, bool)
    state, _ = K.check_and_update_batch(
        state, slots, deltas, maxes, windows, req, np.zeros(8, bool),
        bucket, np.int32(1000),
    )
    state, _ = K.check_and_update_batch(
        state, slots, deltas, maxes, windows, req, np.zeros(8, bool),
        bucket, np.int32(1001),
    )
    assert _hits(state)[2] == 2
    # recycle: the fresh flag must restart attribution at THIS batch
    fresh = np.zeros(8, bool)
    fresh[0] = True
    state, _ = K.check_and_update_batch(
        state, slots, deltas, maxes, windows, req, fresh, bucket,
        np.int32(1002),
    )
    assert _hits(state)[2] == 1


def test_update_lane_accumulates_too():
    state = K.make_table(8)
    slots = np.asarray([4, 4, 5, 8, 8, 8, 8, 8], np.int32)
    deltas = np.asarray([3, 2, 1, 0, 0, 0, 0, 0], np.int32)
    windows = np.asarray([60_000] * 3 + [0] * 5, np.int32)
    state = K.update_batch(
        state, slots, deltas, windows, np.zeros(8, bool),
        np.zeros(8, bool), np.int32(1000),
    )
    hits = _hits(state)
    assert hits[4] == 2 and hits[5] == 1 and hits[-1] == 0


def test_drain_top_hits_ranks_and_resets():
    state = K.make_table(16)
    traffic = {3: 7, 9: 2, 11: 5}
    for slot, count in traffic.items():
        for i in range(count):
            slots = np.full(8, 16, np.int32)
            slots[0] = slot
            deltas = np.zeros(8, np.int32)
            deltas[0] = 1
            state = K.update_batch(
                state, slots, deltas,
                np.full(8, 60_000, np.int32), np.zeros(8, bool),
                np.zeros(8, bool), np.int32(1000 + i),
            )
    new_hits, counts, top = K.drain_top_hits(state.hits, 4)
    counts = np.asarray(counts)
    top = np.asarray(top)
    live = counts > 0
    assert dict(zip(top[live].tolist(), counts[live].tolist())) == traffic
    assert counts[0] == 7 and top[0] == 3  # descending
    assert np.asarray(new_hits).sum() == 0  # read-and-reset
    state = K.CounterTableState(state.values, state.expiry_ms, new_hits)
    _nh, counts2, _top2 = K.drain_top_hits(state.hits, 4)
    assert np.asarray(counts2).sum() == 0


def test_credit_and_clear_semantics():
    state = K.make_table(8)
    slots = np.asarray([1, 8, 8, 8, 8, 8, 8, 8], np.int32)
    deltas = np.asarray([2] + [0] * 7, np.int32)
    windows = np.asarray([60_000] + [0] * 7, np.int32)
    state = K.update_batch(
        state, slots, deltas, windows, np.zeros(8, bool),
        np.zeros(8, bool), np.int32(1000),
    )
    # credits are settlement, not traffic
    state = K.credit_batch(
        state, np.asarray([1], np.int32), np.asarray([1], np.int32),
        np.asarray([60_000], np.int32), np.asarray([False]),
        np.int32(1001),
    )
    assert _hits(state)[1] == 1
    # a cleared slot's history dies with its counter
    state = K.clear_slots(state, np.asarray([1], np.int32))
    assert _hits(state)[1] == 0


# -- storage drain vs oracle -------------------------------------------------


def _identity_of(counter) -> tuple:
    return (
        str(counter.namespace),
        counter.limit.name,
        int(counter.max_value),
        counter.window_seconds,
        tuple(sorted(counter.set_variables.items())),
    )


def _spy_kernel_hits(storage, oracle: TallyCounter):
    """Count every real hit row the storage stages, by counter identity
    resolved at stage time — the host-side oracle the drain must
    match."""
    scratch = storage._scratch

    def tally_slots(slots):
        info = storage._table.info
        for slot in np.asarray(slots).reshape(-1).tolist():
            if slot == scratch:
                continue
            entry = info.get(slot)
            if entry is not None:
                oracle[_identity_of(entry[1])] += 1

    real_check = storage._kernel_check
    real_update = storage._kernel_update
    real_columnar = storage.begin_check_columnar

    def kernel_check(slots, *a, **kw):
        tally_slots(slots)
        return real_check(slots, *a, **kw)

    def kernel_update(slots, *a, **kw):
        tally_slots(slots)
        return real_update(slots, *a, **kw)

    def begin_columnar(slots, *a, **kw):
        tally_slots(slots)
        return real_columnar(slots, *a, **kw)

    storage._kernel_check = kernel_check
    storage._kernel_update = kernel_update
    storage.begin_check_columnar = begin_columnar


def _observed(observatory) -> TallyCounter:
    out = TallyCounter()
    for record in observatory.top(10_000):
        key = (
            record["namespace"], record["limit_name"],
            record["max_value"], record["seconds"],
            tuple(sorted(record["key"].items())),
        )
        out[key] += record["hits"]
    return out


def test_storage_drain_matches_oracle_under_mixed_traffic():
    """check_many over a mixed fixed-window/token-bucket drive with
    rejections and repeats: the drained, attributed counts must equal
    the staged-row oracle EXACTLY."""
    rng = np.random.default_rng(7)
    storage = TpuStorage(capacity=1 << 10)
    fw = Limit("api", 5, 60, [], ["u"], name="fw")
    tb = Limit("tb", 3, 60, [], ["u"], policy="token_bucket", name="tb")
    oracle: TallyCounter = TallyCounter()
    _spy_kernel_hits(storage, oracle)
    observatory = TenantUsageObservatory(storage, top_k=64)
    for _ in range(6):
        reqs = []
        for _ in range(64):
            limit = fw if rng.integers(0, 2) else tb
            user = f"user-{int(rng.integers(0, 9))}"
            reqs.append(_Request([Counter(limit, {"u": user})], 1, False))
        storage.check_many(reqs)
        if rng.integers(0, 2):
            observatory.drain()  # mid-stream drains must not lose counts
    # unconditional updates count too (Report role)
    storage.update_counter(Counter(fw, {"u": "reporter"}), 2)
    observatory.drain()
    observed = _observed(observatory)
    assert observed == oracle
    # quota pressure: rejected-heavy fixed windows sample at >= 100%
    pressure = observatory.pressure()
    assert pressure["top_namespace"] in ("api", "tb")
    assert "api" in pressure["namespaces"]


def test_storage_drain_top_ordering_and_k():
    storage = TpuStorage(capacity=1 << 10)
    limit = Limit("api", 10**6, 60, [], ["u"], name="fw")
    for user, n in (("hot", 40), ("warm", 12), ("cold", 3)):
        for _ in range(n):
            storage.check_many(
                [_Request([Counter(limit, {"u": user})], 1, False)]
            )
    observatory = TenantUsageObservatory(storage, top_k=8)
    observatory.drain()
    top = observatory.top(2)
    assert [r["key"]["u"] for r in top] == ["hot", "warm"]
    assert [r["hits"] for r in top] == [40, 12]


def test_storage_drain_compiles_no_program_per_live_count():
    """The drain's gather runs under the storage lock every drain
    period: its shape must not follow how many slots saw traffic, or
    every count not seen before compiles a new program there (half a
    second each on a TPU)."""
    storage = TpuStorage(capacity=1 << 10)
    limit = Limit("api", 10**6, 60, [], ["u"], name="fw")
    programs = []
    for n_live in (1, 3, 5, 2):
        for user in range(n_live):
            storage.check_many(
                [_Request([Counter(limit, {"u": str(user)})], 1, False)]
            )
        assert len(storage.drain_hot_slots(8)) == n_live
        programs.append(K.read_slots._cache_size())
    assert len(set(programs)) == 1, programs


def test_sharded_drain_attribution_including_globals():
    from limitador_tpu.parallel.mesh import make_mesh
    from limitador_tpu.tpu.sharded import TpuShardedStorage

    storage = TpuShardedStorage(
        mesh=make_mesh(), local_capacity=128, global_region=8,
        global_namespaces=["gns"],
    )
    limiter = RateLimiter(storage)
    limiter.add_limit(Limit("ns", 100, 60, [], ["u"], name="local"))
    limiter.add_limit(Limit("gns", 100, 60, [], [], name="global"))
    for i in range(18):
        limiter.check_rate_limited_and_update(
            "ns", Context({"u": f"user-{i % 3}"}), 1
        )
    for _ in range(5):
        limiter.check_rate_limited_and_update("gns", Context({}), 1)
    records = storage.drain_hot_slots(16)
    by_name = {}
    for record in records:
        key = (record.get("namespace"), tuple(
            sorted((record.get("key") or {}).items())
        ))
        by_name[key] = by_name.get(key, 0) + record["count"]
    assert by_name[("gns", ())] == 5
    for i in range(3):
        assert by_name[("ns", (("u", f"user-{i}"),))] == 6
    # read-and-reset: a second drain is empty
    assert storage.drain_hot_slots(16) == []


# -- native pipeline: fuzz drive + leased merge ------------------------------


def _corpus(seed: int, n: int = 300):
    rng = np.random.default_rng(seed)
    blobs = []
    domains = ["api", "bucket", "mixed", "nolimits", ""]
    for _ in range(n):
        roll = rng.integers(0, 10)
        req = rls_pb2.RateLimitRequest(
            domain=str(domains[int(rng.integers(0, len(domains)))])
        )
        if roll >= 8:
            req.hits_addend = int(rng.integers(0, 4))
        d = req.descriptors.add()
        e = d.entries.add()
        e.key = "m"
        e.value = "GET" if rng.integers(0, 3) else "POST"
        e = d.entries.add()
        e.key = "u"
        e.value = f"user-{int(rng.integers(0, 10))}"
        blobs.append(req.SerializeToString())
        if roll == 9 and blobs:
            blobs.append(blobs[int(rng.integers(0, len(blobs)))])
    return blobs


def _build_pipeline(lease: bool):
    from limitador_tpu.tpu.native_pipeline import NativeRlsPipeline

    limiter = CompiledTpuLimiter(
        AsyncTpuStorage(TpuStorage(capacity=1 << 12), max_delay=0.001)
    )
    for limit in (
        Limit("api", 4, 60, [f"{D}.m == 'GET'"], [f"{D}.u"], name="get"),
        Limit("api", 9, 120, [], [f"{D}.u"], name="user"),
        Limit("bucket", 5, 60, [], [f"{D}.u"], name="tb",
              policy="token_bucket"),
        Limit("mixed", 3, 30, [], [f"{D}.u"], name="fw"),
    ):
        limiter.add_limit(limit)
    pipeline = NativeRlsPipeline(limiter, None, max_delay=0.001,
                                 hot_lane=True)
    assert pipeline.hot_lane_active
    broker = None
    if lease:
        from limitador_tpu.lease import LeaseConfig

        broker = pipeline.attach_lease(
            LeaseConfig(max_tokens=64, hot_threshold=2, ttl_s=30.0),
            autostart=False,
        )
    return pipeline, limiter, broker


@pytest.mark.skipif(
    not native.available(), reason="native hostpath unavailable"
)
def test_debug_top_matches_oracle_fuzz_lease_off():
    """ISSUE 8 acceptance: under a mixed fuzz-corpus drive with leasing
    off, the observatory's counts match the staged-row oracle exactly
    and /debug/top ranks them truthfully."""
    pipeline, _limiter, _ = _build_pipeline(lease=False)
    storage = pipeline.storage
    oracle: TallyCounter = TallyCounter()
    _spy_kernel_hits(storage, oracle)
    observatory = TenantUsageObservatory(
        storage, pipeline=pipeline, top_k=64
    )
    blobs = _corpus(11)
    for ofs in range(0, len(blobs), 64):
        pipeline.decide_many(blobs[ofs:ofs + 64], chunk=64)
        if ofs % 128 == 0:
            observatory.drain()
    payload = observatory.top_counters()
    observed = _observed(observatory)
    assert observed == oracle
    top = payload["top"]
    assert top == sorted(top, key=lambda r: -r["hits"])
    expected_hottest = max(oracle.values())
    assert top[0]["hits"] == expected_hottest


@pytest.mark.skipif(
    not native.available() or not native.lease_available(),
    reason="native lease lane unavailable",
)
def test_debug_top_with_leasing_within_leased_token_bounds():
    """With leasing on, leased rows never reach the device — the native
    merge attributes them, and the only slack left is grant-debit rows
    (one accumulator count per slot per grant) plus tokens still
    outstanding at the final drain."""
    pipeline, _limiter, broker = _build_pipeline(lease=True)
    storage = pipeline.storage
    oracle: TallyCounter = TallyCounter()
    _spy_kernel_hits(storage, oracle)
    observatory = TenantUsageObservatory(
        storage, pipeline=pipeline, top_k=64
    )
    blobs = _corpus(13)
    grant_batches = 0
    for ofs in range(0, len(blobs), 64):
        pipeline.decide_many(blobs[ofs:ofs + 64], chunk=64)
        summary = broker.refresh()
        if summary.get("grants"):
            grant_batches += summary["grants"]
        if ofs % 128 == 0:
            observatory.drain()
    observatory.drain()
    observed = _observed(observatory)
    # Every grant's pre-debit launch staged one row per slot, which the
    # spy counted as oracle traffic but serves leased hits later; the
    # merged view can differ per identity by at most the grants touching
    # it plus one drain interval of stranded counts. Globally: the total
    # must sit within [oracle - outstanding-leases, oracle + grants].
    total_observed = sum(observed.values())
    total_oracle = sum(oracle.values())
    leased = pipeline.lease_stats().get("lease_admissions", 0)
    assert leased > 0, "lease tier never served a hit; bound untested"
    slack = grant_batches * 4 + 64  # grants x max nhits + one interval
    assert abs(total_observed - total_oracle) <= slack, (
        total_observed, total_oracle, slack,
    )
    # /debug/top's per-record over-admission context: live leased debit
    # rides the top records whenever the broker ledger holds tokens
    payload = observatory.top_counters()
    if pipeline.lease_stats().get("lease_outstanding_tokens", 0):
        assert any("lease_outstanding" in r for r in payload["top"]), (
            payload["top"][:3]
        )


@pytest.mark.skipif(
    not native.available() or not native.lease_available(),
    reason="native lease lane unavailable",
)
def test_leased_hits_attribute_through_native_merge():
    """Fully-leased traffic (zero kernel launches) must still attribute:
    the per-plan C counts drain through drain_leased_usage and resolve
    to slots/counters."""
    pipeline, _limiter, _ = _build_pipeline(lease=False)
    lane = pipeline._hot_lane
    req = rls_pb2.RateLimitRequest(domain="api")
    d = req.descriptors.add()
    e = d.entries.add()
    e.key, e.value = "m", "POST"  # only the per-user limit matches
    e = d.entries.add()
    e.key, e.value = "u", "leasee"
    blob = req.SerializeToString()
    pipeline.decide_many([blob], chunk=8)  # derive + mirror
    epoch = pipeline.plan_cache.epoch
    observatory = TenantUsageObservatory(
        pipeline.storage, pipeline=pipeline, top_k=16
    )
    observatory.drain()  # flush the derivation traffic out of the way
    with pipeline._native_lock:
        lane.lease_config(True, 1 << 30)
        assert lane.lease_grant(blob, epoch, 1, 8)
    try:
        for _ in range(5):
            out = pipeline.decide_many([blob], chunk=8)
            assert out[0] is not None
        observatory.drain()
        observed = _observed(observatory)
        leased_counts = [
            count for (ns, name, _mx, _s, key), count in observed.items()
            if ns == "api" and name == "user"
            # the compiled path's variable keys are full CEL paths
            and key == ((f"{D}.u", "leasee"),)
        ]
        assert leased_counts and leased_counts[0] >= 5
    finally:
        with pipeline._native_lock:
            lane.lease_revoke(blob)
            lane.lease_config(False)


# -- control-signal bus ------------------------------------------------------


def test_signals_schema_pins_the_inlined_registries():
    """signals.py inlines the priority and native-phase orders so
    host-only servers never import jax/admission for a schema; this pin
    keeps them in sync with the owning modules."""
    from limitador_tpu.admission.priority import PRIORITIES
    from limitador_tpu.observability.native_plane import PHASES

    assert _PRIORITIES == PRIORITIES
    assert _PHASES == PHASES


def test_signal_bus_snapshot_fields_vector_and_timeline():
    clock = [1000.0]
    bus = SignalBus(timeline=4, clock=lambda: clock[0])

    class FakeRecorder:
        signal_queue_wait_s = 0.004
        signal_batch_fill = 0.5

    bus.attach_recorder(FakeRecorder())

    class FakeBreaker:
        state = "open"

    class FakeAdmission:
        breaker = FakeBreaker()
        _shed_lock = threading.Lock()
        _shed_counts = {("overload", "normal"): 10}

    bus.attach_admission(FakeAdmission())
    first = bus.snapshot()
    assert set(first.to_dict()) == set(ControlSignals.FIELDS)
    assert first.queue_wait_ms == 4.0
    assert first.batch_fill == 0.5
    assert first.breaker_state == 2  # open
    assert first.shed_rate_by_priority["normal"] == 0.0  # no prior tick
    clock[0] += 5.0
    FakeAdmission._shed_counts = {("overload", "normal"): 30}
    second = bus.snapshot()
    assert second.shed_rate_by_priority["normal"] == pytest.approx(4.0)
    assert len(second.vector()) == len(first.vector())
    for _ in range(6):
        clock[0] += 1.0
        bus.snapshot()
    assert len(bus.timeline()) == 4  # ring bounded
    payload = bus.signals_debug()
    assert payload["fields"] == list(ControlSignals.FIELDS)
    assert payload["current"]["ts"] >= second.ts


def test_signal_bus_feeds_metrics_families():
    from limitador_tpu.observability.metrics import PrometheusMetrics

    storage = TpuStorage(capacity=1 << 8)
    limit = Limit("api", 100, 60, [], ["u"], name="fw")
    storage.check_many(
        [_Request([Counter(limit, {"u": "x"})], 1, False)] * 3
    )
    bus = SignalBus()
    observatory = TenantUsageObservatory(storage, top_k=8, signal_bus=bus)
    bus.attach_observatory(observatory)
    observatory.drain()
    metrics = PrometheusMetrics()
    metrics.attach_render_hook(observatory)
    metrics.attach_render_hook(bus)
    text = metrics.render().decode()
    assert 'tenant_hits_total{limitador_namespace="api"} 3.0' in text
    assert "tenant_tracked_counters 1.0" in text
    assert "signal_queue_wait_ms" in text
    assert 'signal_shed_rate{priority="normal"}' in text
    # a second render must not double-count the cumulative hits
    text = metrics.render().decode()
    assert 'tenant_hits_total{limitador_namespace="api"} 3.0' in text


def test_observatory_thread_drains_and_ticks_the_bus():
    storage = TpuStorage(capacity=1 << 8)
    limit = Limit("api", 100, 60, [], ["u"], name="fw")
    bus = SignalBus()
    observatory = TenantUsageObservatory(
        storage, top_k=8, interval_s=0.02, signal_bus=bus
    )
    observatory.start()
    try:
        storage.check_many(
            [_Request([Counter(limit, {"u": "x"})], 1, False)] * 4
        )
        deadline = time.time() + 5.0
        while time.time() < deadline:
            if _observed(observatory).total() == 4 and bus.timeline():
                break
            time.sleep(0.02)
        assert _observed(observatory).total() == 4
        assert bus.timeline(), "the drain thread never ticked the bus"
    finally:
        observatory.close()


# -- HTTP surface ------------------------------------------------------------


def test_debug_top_and_signals_endpoints():
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from limitador_tpu.server.http_api import make_http_app

    storage = TpuStorage(capacity=1 << 8)
    limit = Limit("api", 100, 60, [], ["u"], name="fw")
    storage.check_many(
        [_Request([Counter(limit, {"u": "x"})], 1, False)] * 5
    )
    bus = SignalBus()
    observatory = TenantUsageObservatory(storage, top_k=8, signal_bus=bus)
    bus.attach_observatory(observatory)

    async def main():
        app = make_http_app(
            RateLimiter(), None, {}, debug_sources=[observatory, bus]
        )
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            top = await (await client.get("/debug/top")).json()
            signals = await (await client.get("/debug/signals")).json()
            stats = await (await client.get("/debug/stats")).json()
            bad = (await client.get("/debug/top?k=x")).status
        finally:
            await client.close()
        return top, signals, stats, bad

    loop = asyncio.new_event_loop()
    try:
        top, signals, stats, bad = loop.run_until_complete(main())
    finally:
        loop.close()
    assert top["top"][0]["hits"] == 5
    assert top["top"][0]["namespace"] == "api"
    assert top["top"][0]["key"] == {"u": "x"}
    assert set(signals["current"]) == set(ControlSignals.FIELDS)
    assert signals["current"]["top_namespace"] == "api"
    assert "tenant_usage" in stats and "signals" in stats
    assert stats["tenant_usage"]["tracked_counters"] == 1
    assert bad == 400


def test_debug_top_404_without_observatory():
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from limitador_tpu.server.http_api import make_http_app

    async def main():
        app = make_http_app(RateLimiter(), None, {})
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            return (
                (await client.get("/debug/top")).status,
                (await client.get("/debug/signals")).status,
            )
        finally:
            await client.close()

    loop = asyncio.new_event_loop()
    try:
        top_status, signals_status = loop.run_until_complete(main())
    finally:
        loop.close()
    assert top_status == 404 and signals_status == 404


def test_debug_sections_registry_covers_served_sections():
    """The lint gate's registry (http_api.DEBUG_STATS_SECTIONS) and the
    source-section tuple must agree — and the lint itself must pass on
    the live tree."""
    from pathlib import Path

    from limitador_tpu.server.http_api import (
        DEBUG_SOURCE_SECTIONS,
        DEBUG_STATS_SECTIONS,
    )
    from limitador_tpu.tools.lint import lint_debug_sections

    for key, _attr in DEBUG_SOURCE_SECTIONS:
        assert key in DEBUG_STATS_SECTIONS
    repo_root = Path(__file__).resolve().parent.parent
    assert lint_debug_sections(repo_root) == []
