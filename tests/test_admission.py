"""Admission plane: breaker, AIMD overload control, priority shedding,
host failover and the device-hang chaos drill.

The acceptance bar (ISSUE 2): with the device plane forcibly hung under
load, the check path keeps answering with exact host-plane decisions
(nothing blocks on the dead plane); on recovery the breaker closes and
a device-vs-host reconcile check passes with zero lost deltas. Plus the
property that a shed is never an erroneous OK and never occupies a
batch slot.
"""

import asyncio
import threading
import time

import pytest

from limitador_tpu import AsyncRateLimiter, Context, Limit
from limitador_tpu.admission import (
    AdaptiveLimiter,
    AdmissionController,
    AdmissionShed,
    BreakerState,
    CircuitBreaker,
    PriorityResolver,
)
from limitador_tpu.storage.base import StorageError
from limitador_tpu.storage.failover import FailoverStore
from limitador_tpu.tpu.batcher import AsyncTpuStorage
from limitador_tpu.tpu.storage import TpuStorage


def run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


class FakeClock:
    def __init__(self):
        self.now = 1000.0

    def __call__(self):
        return self.now

    def advance(self, seconds):
        self.now += seconds


# -- circuit breaker ---------------------------------------------------------


def test_breaker_full_lifecycle():
    clock = FakeClock()
    b = CircuitBreaker(
        failure_threshold=2, stall_timeout=1.0, reset_timeout=5.0,
        clock=clock,
    )
    assert b.state == BreakerState.CLOSED and not b.is_open()
    b.record_failure(StorageError("boom", transient=True))
    assert b.state == BreakerState.CLOSED
    b.record_failure(StorageError("boom", transient=True))
    assert b.state == BreakerState.OPEN and b.is_open()
    # reset dwell -> half-open; only one probe claim
    clock.advance(5.1)
    assert b.is_open()  # half-open still keeps the check path host-side
    assert b.state == BreakerState.HALF_OPEN
    assert b.try_claim_probe()
    assert not b.try_claim_probe()
    # failed probe -> open again, then a later successful probe closes
    b.record_failure(StorageError("still dead", transient=True))
    assert b.state == BreakerState.OPEN
    clock.advance(5.1)
    assert b.try_claim_probe()
    # a mere batch success must NOT close a half-open breaker (it may
    # be a pre-trip batch completing late, skipping the reconcile);
    # only the probe protocol closes.
    b.record_success()
    assert b.state == BreakerState.HALF_OPEN
    b.probe_succeeded()
    assert b.state == BreakerState.CLOSED and not b.is_open()
    # open+half-open time accrued exactly once
    assert b.open_seconds_total() == pytest.approx(10.2, abs=0.01)


def test_breaker_stall_trip_and_non_storage_errors_ignored():
    clock = FakeClock()
    b = CircuitBreaker(stall_timeout=0.5, clock=clock)
    b.record_success()  # warmed: steady-state stall watch applies
    # caller bugs must never open the plane
    for _ in range(10):
        b.record_failure(ValueError("negative delta"))
    assert b.state == BreakerState.CLOSED
    token = b.batch_started()
    clock.advance(0.2)
    assert not b.check_stall()
    clock.advance(0.4)  # in-flight batch now 0.6s old
    assert b.check_stall()
    assert b.state == BreakerState.OPEN
    assert "stalled" in (b.last_error() or "")
    b.batch_finished(token)  # late completion must not flip state
    assert b.state == BreakerState.OPEN


def test_breaker_warmup_grace_spares_the_compile_batch():
    """The first-ever device batch carries XLA compilation and can
    exceed the steady-state stall timeout; until a batch has succeeded
    the stall watch uses the warmup bound instead — but a plane dead AT
    boot still trips once that bound passes."""
    clock = FakeClock()
    b = CircuitBreaker(
        stall_timeout=0.5, warmup_stall_timeout=10.0, clock=clock
    )
    token = b.batch_started()
    clock.advance(5.0)  # compile-sized, way past the steady stall
    assert not b.check_stall()
    assert b.state == BreakerState.CLOSED
    b.batch_finished(token)  # compile done, plane warmed
    token = b.batch_started()
    clock.advance(0.6)
    assert b.check_stall()  # steady-state watch now applies
    assert b.state == BreakerState.OPEN
    # and dead-at-boot still trips eventually
    b2 = CircuitBreaker(
        stall_timeout=0.5, warmup_stall_timeout=10.0, clock=clock
    )
    b2.batch_started()
    clock.advance(10.1)
    assert b2.check_stall()


def test_failed_probe_rearms_the_reset_dwell():
    clock = FakeClock()
    b = CircuitBreaker(failure_threshold=1, reset_timeout=5.0, clock=clock)
    b.record_failure(StorageError("x", transient=True))
    clock.advance(5.1)
    assert b.try_claim_probe()
    b.record_failure(StorageError("still dead", transient=True))
    # a failed probe must re-arm the FULL dwell, not re-probe next tick
    clock.advance(2.0)
    assert not b.try_claim_probe()
    clock.advance(3.2)
    assert b.try_claim_probe()


def test_stale_inflight_tokens_cleared_on_trip():
    """A batch wedged forever on the dead plane must not re-trip the
    stall watch the instant the breaker recovers."""
    clock = FakeClock()
    b = CircuitBreaker(stall_timeout=0.5, reset_timeout=1.0, clock=clock)
    b.record_success()  # warmed
    b.batch_started()   # this batch will never finish
    clock.advance(0.6)
    assert b.check_stall()
    clock.advance(1.1)
    assert b.try_claim_probe()
    b.probe_succeeded()
    assert b.state == BreakerState.CLOSED
    clock.advance(10.0)  # the wedged batch's token is ancient by now
    assert not b.check_stall(), "stale pre-trip token re-tripped the breaker"
    assert b.state == BreakerState.CLOSED


def test_breaker_consecutive_failures_reset_by_success():
    b = CircuitBreaker(failure_threshold=3)
    b.record_failure(StorageError("x", transient=True))
    b.record_failure(StorageError("x", transient=True))
    b.record_success()
    b.record_failure(StorageError("x", transient=True))
    b.record_failure(StorageError("x", transient=True))
    assert b.state == BreakerState.CLOSED


# -- AIMD overload control ---------------------------------------------------


def test_aimd_backs_off_and_recovers():
    clock = FakeClock()
    lim = AdaptiveLimiter(
        max_inflight=100, min_limit=4, target_queue_wait=0.01,
        adjust_interval=0.1, backoff=0.5, clock=clock,
    )
    assert lim.limit == 100
    # sustained congestion: multiplicative decrease per interval
    for _ in range(3):
        clock.advance(0.2)
        lim.observe(0.5)
    assert lim.limit == 12  # 100 -> 50 -> 25 -> 12
    # never below min_limit under continued congestion
    for _ in range(50):
        clock.advance(0.2)
        lim.observe(1.0)
    assert lim.limit == 4
    # calm queue: once the EWMA decays under target, additive increase
    for _ in range(40):
        clock.advance(0.2)
        lim.observe(0.0)
    assert lim.limit > 4
    assert lim.queue_wait_estimate() < 0.01


def test_priority_shares_shed_low_first():
    lim = AdaptiveLimiter(max_inflight=10, min_limit=1)
    # saturate to 6/10 in flight (critical ignores class shares)
    for _ in range(6):
        assert lim.try_acquire(3)
    assert not lim.try_acquire(0)   # low caps at 50% of the limit
    assert lim.try_acquire(1)       # normal caps at 75%: 7/10
    assert lim.try_acquire(1)       # 8/10 (7 < 7.5 still admitted)
    assert not lim.try_acquire(1)   # 8 >= 7.5: normal sheds
    assert lim.try_acquire(2)       # high caps at 90%: 9/10
    assert not lim.try_acquire(2)   # 9 >= 9: high sheds
    assert lim.try_acquire(3)       # critical rides to the full limit
    assert not lim.try_acquire(3)   # hard ceiling


# -- priority resolution -----------------------------------------------------


def test_priority_resolver_precedence():
    r = PriorityResolver(
        descriptor_key="prio", namespace_map={"payments": 3}, default=1
    )
    r.refresh([
        Limit("api", 10, 60, [], ["u"], priority="high"),
        Limit("api", 99, 3600, [], ["u"]),
        Limit("batch", 10, 60, [], [], priority="low"),
    ])
    # descriptor entry wins
    assert r.resolve("api", {"prio": "critical"}) == 3
    assert r.resolve("api", {"prio": "0"}) == 0
    # unknown descriptor value falls through to annotations
    assert r.resolve("api", {"prio": "wat"}) == 2
    # CLI map beats annotations; annotation max; default
    assert r.resolve("payments", {}) == 3
    assert r.resolve("batch", None) == 0
    assert r.resolve("elsewhere", {}) == 1


def test_limit_priority_annotation_roundtrip_and_identity():
    a = Limit("ns", 10, 60, [], ["u"], priority="critical")
    b = Limit("ns", 10, 60, [], ["u"])
    assert a == b and hash(a) == hash(b)  # not part of identity
    assert a.to_dict()["priority"] == "critical"
    assert "priority" not in b.to_dict()
    assert Limit.from_dict(a.to_dict()).priority == "critical"
    with pytest.raises(ValueError):
        Limit("ns", 10, 60, priority="urgent")


# -- failover store ----------------------------------------------------------


def test_failover_journal_reconciles_into_device_table():
    store = FailoverStore()
    device = TpuStorage(capacity=1 << 8)
    limit = Limit("ns", 100, 3600, [], ["u"])
    device.add_counter(limit)
    from limitador_tpu.core.counter import Counter

    c = Counter(limit, {"u": "a"})
    # 3 admitted failover decisions journal 3 deltas
    for _ in range(3):
        auth = store.check_and_update([c.key()], 1, False)
        assert not auth.limited
    # limited decisions journal nothing
    assert store.check_and_update([c.key()], 98, False).limited
    assert store.journal_size() == 1
    applied = store.reconcile_into(device)
    assert applied == 1
    assert store.journal_size() == 0
    # device agrees: 3 spent, 97 headroom, not 98
    assert device.is_within_limits(c, 97)
    assert not device.is_within_limits(c, 98)
    # oracle cleared: a fresh failover window starts from zero
    assert store.check_and_update([c.key()], 100, False).limited is False


def test_failover_reconcile_failure_restores_journal():
    store = FailoverStore()
    from limitador_tpu.core.counter import Counter

    limit = Limit("ns", 100, 3600, [], ["u"])
    store.check_and_update([Counter(limit, {"u": "a"})], 2, False)

    class Broken:
        def apply_deltas(self, items):
            raise StorageError("device gone again", transient=True)

    with pytest.raises(StorageError):
        store.reconcile_into(Broken())
    assert store.journal_size() == 1  # nothing lost


# -- shedding ----------------------------------------------------------------


def test_shed_is_never_an_ok_and_takes_no_batch_slot():
    """Property: across randomized admission states, admit() either
    returns a ticket or raises AdmissionShed — and a shed consumes no
    in-flight slot and no batcher queue entry."""
    import random

    rng = random.Random(7)
    for _trial in range(200):
        max_inflight = rng.randint(1, 20)
        lim = AdaptiveLimiter(max_inflight=max_inflight, min_limit=1)
        adm = AdmissionController(mode="enforce", overload=lim)
        pre = rng.randint(0, max_inflight)
        taken = [lim.try_acquire(3) for _ in range(pre)]
        held = sum(taken)
        if rng.random() < 0.5:
            lim.observe(rng.uniform(0.0, 0.1))
        deadline = rng.choice([None, 0.0, 0.0005, 10.0])
        priority = rng.randint(0, 3)
        try:
            ticket = adm.admit("ns", {"priority": str(priority)}, deadline)
        except AdmissionShed as shed:
            # the shed took nothing: inflight unchanged
            assert lim.inflight == held
            assert shed.reason in ("deadline", "overload")
            assert shed.transient
        else:
            assert lim.inflight == held + 1
            ticket.release()
            ticket.release()  # idempotent
            assert lim.inflight == held


def test_enforced_shed_short_circuits_before_the_batcher():
    """A shed request must never reach the micro-batcher (no batch slot
    consumed) and must never come back OK."""
    from limitador_tpu.server.proto import rls_pb2
    from limitador_tpu.server.rls import RlsService

    async def main():
        storage = AsyncTpuStorage(TpuStorage(capacity=1 << 8),
                                  max_delay=0.001)
        limiter = AsyncRateLimiter(storage)
        limiter.add_limit(Limit("api", 100, 60, [], ["u"]))
        lim = AdaptiveLimiter(max_inflight=1, min_limit=1)
        adm = AdmissionController(
            mode="enforce", overload=lim, shed_response="overlimit"
        )
        storage.set_admission(adm)
        while lim.try_acquire(3):  # saturate: everything sheds now
            pass
        service = RlsService(limiter, admission=adm)
        req = rls_pb2.RateLimitRequest(domain="api")
        d = req.descriptors.add()
        e = d.entries.add()
        e.key, e.value = "u", "x"

        class Ctx:
            def invocation_metadata(self):
                return ()

            async def abort(self, code, details=""):
                raise AssertionError("overlimit mode must not abort")

        resp = await service.should_rate_limit(req, Ctx())
        assert resp.overall_code == rls_pb2.RateLimitResponse.OVER_LIMIT
        # no batch slot was consumed: the batcher never even started
        assert storage.batcher._pending == []
        assert storage.batcher._task is None
        await storage.close()

    run(main())


def test_deadline_doomed_requests_shed_before_admission():
    lim = AdaptiveLimiter(max_inflight=10, min_limit=1)
    adm = AdmissionController(mode="enforce", overload=lim)
    lim.observe(0.050)  # queue-wait estimate ~50ms
    with pytest.raises(AdmissionShed) as exc:
        adm.admit("ns", None, deadline=0.010)
    assert exc.value.reason == "deadline"
    assert lim.inflight == 0  # doomed request took no slot
    ticket = adm.admit("ns", None, deadline=10.0)
    ticket.release()


def test_monitor_mode_counts_sheds_but_admits():
    lim = AdaptiveLimiter(max_inflight=1, min_limit=1)
    adm = AdmissionController(mode="monitor", overload=lim)
    assert lim.try_acquire(3)  # saturate
    ticket = adm.admit("ns", None, None)  # would shed; admitted anyway
    assert ticket is not None
    debug = adm.admission_debug()
    assert sum(
        n for k, n in debug["sheds"].items() if k.startswith("overload")
    ) == 1
    assert debug["recent_sheds"][-1]["enforced"] is False


# -- the chaos drill ---------------------------------------------------------


class HangableStorage(TpuStorage):
    """TpuStorage whose device->host collect path can be wedged: the
    hung-device_sync failure mode."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self._gate = threading.Event()
        self._gate.set()

    def hang(self):
        self._gate.clear()

    def unhang(self):
        self._gate.set()

    def finish_check_many(self, handle):
        self._gate.wait()
        return super().finish_check_many(handle)


def test_chaos_device_hang_failover_recovery_reconcile():
    """The acceptance drill: hang the device plane under load; the
    breaker trips, every request settles (host decisions or transient
    errors — nothing blocks), the failover window enforces limits
    EXACTLY host-side; after the plane returns the breaker closes and
    a device-vs-host reconcile check passes with zero lost deltas.

    Two counters make the ledger provable: ``bulk`` (huge budget — the
    device kernel admits every in-flight delta, so the final device
    value is an exact sum of known terms) and ``tight`` (budget 120,
    touched only during failover — its post-reconcile device value must
    equal the host-admitted count exactly)."""
    device = HangableStorage(capacity=1 << 8)
    bulk = Limit("bulk", 100_000, 3600, [], ["u"], name="bulk")
    tight = Limit("tight", 120, 3600, [], ["u"], name="tight")

    async def main():
        storage = AsyncTpuStorage(device, max_delay=0.001)
        limiter = AsyncRateLimiter(storage)
        limiter.add_limit(bulk)
        limiter.add_limit(tight)

        async def check(ns):
            try:
                r = await limiter.check_rate_limited_and_update(
                    ns, Context({"u": "shared"}), 1
                )
                return "over" if r.limited else "ok"
            except StorageError:
                return "error"

        # Warm the kernel BEFORE arming the breaker: the first device
        # batch includes XLA compilation, which would trip a 250ms
        # stall watch spuriously.
        assert await check("bulk") == "ok"

        adm = AdmissionController(
            mode="enforce",
            breaker=CircuitBreaker(
                failure_threshold=2, stall_timeout=0.25, reset_timeout=0.2
            ),
            watchdog_tick=0.05,
        )
        storage.set_admission(adm)
        adm.start(asyncio.get_running_loop())

        # Phase A: healthy device plane, 99 more admitted on device.
        a = [await check("bulk") for _ in range(99)]
        assert a == ["ok"] * 99

        # Phase B: wedge the plane, fire staggered concurrent load.
        # EVERY request must settle quickly — host decisions for queued
        # ones, transient errors for those already riding a dead batch.
        device.hang()

        async def staggered(i):
            await asyncio.sleep(0.0 if i < 5 else 0.06 if i < 10 else 0.12)
            return await check("bulk")

        t0 = time.perf_counter()
        b = await asyncio.wait_for(
            asyncio.gather(*[staggered(i) for i in range(40)]), timeout=10.0
        )
        settle_time = time.perf_counter() - t0
        assert settle_time < 5.0, "requests blocked on the dead plane"
        assert adm.breaker.state != BreakerState.CLOSED
        errors_b = b.count("error")
        oks_b = b.count("ok")
        assert errors_b + oks_b + b.count("over") == 40
        assert errors_b >= 1   # the dispatched batch riding the dead plane
        assert oks_b >= 1      # queued requests drained to host decisions

        # Phase C: breaker open — exact host-oracle decisions on a
        # fresh counter: its 120 budget admits exactly 120 of 150.
        c = [await check("tight") for _ in range(150)]
        assert "error" not in c
        assert c.count("ok") == 120, "failover window must enforce exactly"
        assert c[-1] == "over"
        assert adm.failover.journal_size() == 2  # bulk + tight

        # /debug/stats carries the admission section
        from limitador_tpu.observability.device_plane import (
            collect_debug_stats,
        )

        stats = collect_debug_stats(storage)
        assert stats["admission"]["breaker"]["state"] in ("open", "half_open")
        assert stats["admission"]["failover"]["decisions"] > 0

        # Recovery: un-wedge; the watchdog probe succeeds, reconciles
        # the journal into the device table, closes the breaker.
        device.unhang()
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            if adm.breaker.state == BreakerState.CLOSED:
                break
            await asyncio.sleep(0.05)
        assert adm.breaker.state == BreakerState.CLOSED
        assert adm.failover.journal_size() == 0
        assert adm.failover.reconciled_deltas == 2

        # Zero lost deltas, counter by counter. bulk: 100 pre-hang +
        # every in-flight delta the kernel applied (their requests
        # errored) + every host-admitted delta (journal, reconciled).
        def device_value(limit):
            counters = device.get_counters({limit})
            assert len(counters) == 1
            return limit.max_value - next(iter(counters)).remaining

        assert device_value(bulk) == 100 + errors_b + oks_b
        # tight: exactly the 120 host-admitted deltas, nothing lost.
        assert device_value(tight) == 120

        # And the plane serves from the device again.
        assert await check("bulk") == "ok"
        await adm.close()
        await storage.close()

    run(main())


def test_compiled_pipeline_fails_over_when_breaker_open():
    from limitador_tpu.tpu.pipeline import CompiledTpuLimiter

    async def main():
        device = HangableStorage(capacity=1 << 8)
        storage = AsyncTpuStorage(device, max_delay=0.001)
        adm = AdmissionController(
            mode="enforce",
            breaker=CircuitBreaker(stall_timeout=0.25, reset_timeout=60),
        )
        storage.set_admission(adm)
        limiter = CompiledTpuLimiter(storage)
        adm.add_drainable(limiter)
        limiter.add_limit(Limit("api", 5, 3600, [], ["descriptors[0].u"]))
        r = await limiter.check_rate_limited_and_update(
            "api", {"u": "a"}, 1
        )
        assert not r.limited
        adm.breaker.trip("test")
        # compiled fast path must not touch the device now
        outs = [
            await limiter.check_rate_limited_and_update("api", {"u": "a"}, 1)
            for _ in range(6)
        ]
        assert [o.limited for o in outs] == [False] * 5 + [True]
        assert adm.failover.journal_size() == 1
        await adm.close()
        await limiter.close()
        await storage.close()

    run(main())


def test_grpc_shed_semantics_end_to_end():
    """Over a real socket: an overload shed answers OVER_LIMIT in
    overlimit mode; a deadline-doomed request (real gRPC deadline vs a
    forced queue-wait estimate) answers UNAVAILABLE in the default
    mode. Neither ever answers OK."""
    import socket

    import grpc

    from limitador_tpu.server.proto import rls_pb2
    from limitador_tpu.server.rls import serve_rls

    def free_port():
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    def boot(loop, adm):
        storage = AsyncTpuStorage(TpuStorage(capacity=1 << 8),
                                  max_delay=0.001)
        storage.set_admission(adm)
        limiter = AsyncRateLimiter(storage)
        limiter.add_limit(Limit("api", 100, 60, [], ["descriptors[0].u"]))
        port = free_port()
        server = loop.run_until_complete(
            serve_rls(limiter, f"127.0.0.1:{port}", admission=adm)
        )
        return port, server, storage

    def req():
        r = rls_pb2.RateLimitRequest(domain="api")
        d = r.descriptors.add()
        e = d.entries.add()
        e.key, e.value = "u", "x"
        return r

    def call(port, timeout):
        ch = grpc.insecure_channel(f"127.0.0.1:{port}")
        try:
            return ch.unary_unary(
                "/envoy.service.ratelimit.v3.RateLimitService"
                "/ShouldRateLimit",
                request_serializer=(
                    rls_pb2.RateLimitRequest.SerializeToString
                ),
                response_deserializer=(
                    rls_pb2.RateLimitResponse.FromString
                ),
            )(req(), timeout=timeout)
        finally:
            ch.close()

    loop = asyncio.new_event_loop()
    # overload shed, overlimit semantics
    lim = AdaptiveLimiter(max_inflight=1, min_limit=1)
    adm = AdmissionController(
        mode="enforce", overload=lim, shed_response="overlimit"
    )
    port, server, storage = boot(loop, adm)
    t = threading.Thread(target=loop.run_forever, daemon=True)
    t.start()
    try:
        assert call(port, 5).overall_code == rls_pb2.RateLimitResponse.OK
        while lim.try_acquire(3):
            pass
        resp = call(port, 5)
        assert resp.overall_code == rls_pb2.RateLimitResponse.OVER_LIMIT
        # deadline shed, unavailable semantics: free the limiter but
        # force a queue-wait estimate far above the client deadline
        while lim.inflight:
            lim.release()
        adm.shed_overlimit = False
        lim.observe(5.0)
        import pytest as _pytest

        with _pytest.raises(grpc.RpcError) as exc:
            call(port, 0.5)
        assert exc.value.code() == grpc.StatusCode.UNAVAILABLE
        debug = adm.admission_debug()
        assert debug["sheds"]
    finally:
        asyncio.run_coroutine_threadsafe(
            server.stop(grace=None), loop
        ).result(timeout=10)
        asyncio.run_coroutine_threadsafe(
            storage.close(), loop
        ).result(timeout=10)
        loop.call_soon_threadsafe(loop.stop)
        t.join(timeout=5)
        loop.close()


def test_update_path_fails_over_and_reconciles():
    async def main():
        device = HangableStorage(capacity=1 << 8)
        storage = AsyncTpuStorage(device, max_delay=0.001)
        adm = AdmissionController(mode="monitor")
        storage.set_admission(adm)
        limiter = AsyncRateLimiter(storage)
        limit = Limit("api", 100, 3600, [], ["u"])
        limiter.add_limit(limit)
        adm.breaker.trip("test")
        await limiter.update_counters("api", Context({"u": "r"}), 7)
        assert adm.failover.journal_size() == 1
        applied = adm.failover.reconcile_into(device)
        assert applied == 1
        from limitador_tpu.core.counter import Counter

        assert device.is_within_limits(Counter(limit, {"u": "r"}), 93)
        assert not device.is_within_limits(Counter(limit, {"u": "r"}), 94)
        await adm.close()
        await storage.close()

    run(main())
