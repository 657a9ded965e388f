"""TPU backend parity and batch-exactness tests.

The north-star contract (BASELINE.json): exact parity with InMemoryStorage.
Two layers of evidence:

1. Randomized op-stream equivalence: the same sequence of
   check_and_update / update / is_within_limits / expiry jumps produces
   identical admissions, remainings and ttls on both backends (shared fake
   clock).
2. Batched-kernel exactness: a full device batch of concurrent requests
   must decide admission exactly as if the requests were processed
   serially (the reference's semantics under its storage lock), including
   multi-counter requests with cross-slot coupling.
"""

import random

import numpy as np
import pytest

from limitador_tpu import Context, Limit, RateLimiter
from limitador_tpu.storage.in_memory import InMemoryStorage
from limitador_tpu.tpu.storage import TpuStorage, _bucket
from limitador_tpu.ops import kernel as K


class FakeClock:
    def __init__(self):
        self.now = 1_700_000_000.0

    def __call__(self):
        return self.now

    def advance(self, s):
        self.now += s


def make_pair():
    clock = FakeClock()
    mem = RateLimiter(InMemoryStorage(10_000, clock=clock))
    tpu_storage = TpuStorage(capacity=1 << 12, clock=clock)
    tpu = RateLimiter(tpu_storage)
    return clock, mem, tpu


LIMITS = [
    Limit("ns", 5, 60, ["m == 'GET'"], ["u"], name="l5"),
    Limit("ns", 12, 10, [], ["u"], name="l12"),
    Limit("ns", 30, 3600, [], [], name="l30"),
    Limit("ns2", 3, 1, [], ["u"]),
]


def test_randomized_op_stream_parity():
    clock, mem, tpu = make_pair()
    for limiter in (mem, tpu):
        for lim in LIMITS:
            limiter.add_limit(lim)

    rng = random.Random(42)
    users = [str(i) for i in range(6)]
    methods = ["GET", "POST"]

    for step in range(400):
        op = rng.random()
        ns = "ns" if rng.random() < 0.8 else "ns2"
        ctx_vals = {"m": rng.choice(methods), "u": rng.choice(users)}
        delta = rng.choice([1, 1, 1, 2, 5])
        if op < 0.6:
            load = rng.random() < 0.5
            r1 = mem.check_rate_limited_and_update(ns, Context(ctx_vals), delta, load)
            r2 = tpu.check_rate_limited_and_update(ns, Context(ctx_vals), delta, load)
            assert r1.limited == r2.limited, f"step {step}: admission diverged"
            assert r1.limit_name == r2.limit_name, f"step {step}: name diverged"
            if load:
                # ttl compared with 2ms tolerance: the device quantizes
                # expiry to int milliseconds, the oracle keeps float seconds.
                k1 = sorted((c.set_variables.get("u", ""), c.window_seconds,
                             c.remaining, c.expires_in) for c in r1.counters)
                k2 = sorted((c.set_variables.get("u", ""), c.window_seconds,
                             c.remaining, c.expires_in) for c in r2.counters)
                assert len(k1) == len(k2), f"step {step}: counter count diverged"
                for a, b in zip(k1, k2):
                    assert a[:3] == b[:3], f"step {step}: loaded counters diverged"
                    assert abs(a[3] - b[3]) <= 0.002, f"step {step}: ttl diverged"
        elif op < 0.75:
            mem.update_counters(ns, Context(ctx_vals), delta)
            tpu.update_counters(ns, Context(ctx_vals), delta)
        elif op < 0.9:
            r1 = mem.is_rate_limited(ns, Context(ctx_vals), delta)
            r2 = tpu.is_rate_limited(ns, Context(ctx_vals), delta)
            assert r1.limited == r2.limited, f"step {step}: is_rate_limited diverged"
        else:
            clock.advance(rng.choice([0.3, 1.0, 5.0, 11.0]))

    # Final state: counters agree (ttl within ms quantization)
    for ns in ("ns", "ns2"):
        c1 = {(tuple(c.set_variables.items()), c.window_seconds):
              (c.remaining, c.expires_in) for c in mem.get_counters(ns)}
        c2 = {(tuple(c.set_variables.items()), c.window_seconds):
              (c.remaining, c.expires_in) for c in tpu.get_counters(ns)}
        assert c1.keys() == c2.keys()
        for k in c1:
            assert c1[k][0] == c2[k][0], f"{ns} {k}: remaining diverged"
            assert abs(c1[k][1] - c2[k][1]) <= 0.002, f"{ns} {k}: ttl diverged"


def _serial_oracle(batch, values, expiry, now_ms):
    """Reference semantics: process requests in order, each all-or-nothing."""
    values = dict(values)
    expiry = dict(expiry)
    admitted = []
    for hits in batch:  # hits: list of (slot, delta, maxv, window_ms)
        ok = True
        for slot, delta, maxv, _win in hits:
            v = 0 if now_ms >= expiry.get(slot, 0) else values.get(slot, 0)
            if v + delta > maxv:
                ok = False
                break
        if ok:
            for slot, delta, _maxv, win in hits:
                if now_ms >= expiry.get(slot, 0):
                    values[slot] = delta
                    expiry[slot] = now_ms + win
                else:
                    values[slot] = values.get(slot, 0) + delta
        admitted.append(ok)
    return admitted, values, expiry


def _run_kernel(batch, capacity, now_ms, state=None):
    nhits = sum(len(h) for h in batch)
    H = _bucket(max(nhits, 1))
    slots = np.full(H, capacity, np.int32)
    deltas = np.zeros(H, np.int32)
    maxes = np.full(H, np.iinfo(np.int32).max, np.int32)
    windows = np.zeros(H, np.int32)
    req = np.full(H, H - 1, np.int32)
    fresh = np.zeros(H, bool)
    i = 0
    for r, hits in enumerate(batch):
        for slot, delta, maxv, win in hits:
            slots[i], deltas[i], maxes[i], windows[i], req[i] = (
                slot, delta, maxv, win, r)
            i += 1
    if state is None:
        state = K.make_table(capacity)
    state, result = K.check_and_update_batch(
        state, slots, deltas, maxes, windows, req, fresh,
        np.zeros(H, bool), np.int32(now_ms))
    return state, np.asarray(result.admitted)[: len(batch)]


@pytest.mark.parametrize("seed", range(8))
def test_batch_exactness_vs_serial_oracle(seed):
    """Random contended batches, incl. multi-counter cross-slot coupling."""
    rng = random.Random(seed)
    capacity = 32
    now_ms = 10_000
    state = K.make_table(capacity)
    values = {}
    expiry = {}

    for round_i in range(6):
        batch = []
        for _ in range(rng.randint(5, 40)):
            nhits = rng.randint(1, 3)
            used = rng.sample(range(capacity), nhits)
            hits = [
                (s, rng.choice([1, 1, 2]), rng.choice([3, 5, 8]), 60_000)
                for s in used
            ]
            batch.append(hits)
        want, values, expiry = _serial_oracle(batch, values, expiry, now_ms)
        state, got = _run_kernel(batch, capacity, now_ms, state)
        assert list(got) == want, f"seed {seed} round {round_i}"
        now_ms += rng.choice([0, 1_000, 61_000])
        # Oracle state stays as computed; device state carried over.


def test_batch_single_slot_contention_admits_exactly_max():
    """512 concurrent single-hit requests on one key with max 100 -> exactly
    the first 100 admitted (never over- or under-admit)."""
    batch = [[(7, 1, 100, 60_000)] for _ in range(512)]
    _state, got = _run_kernel(batch, capacity=16, now_ms=1000)
    assert got.sum() == 100
    assert got[:100].all() and not got[100:].any()


def _serial_oracle_mixed(batch, values, expiry, now_ms):
    """``_serial_oracle`` over both lanes: a hit is (slot, delta, max,
    window_or_interval, is_bucket); a bucket cell's expiry entry is its
    TAT (storage/gcra.py: conforms iff spent + delta <= B)."""
    admitted = []
    for hits in batch:
        ok = True
        for slot, delta, maxv, win, is_bucket in hits:
            if is_bucket:
                base_rel = max(expiry.get(slot, 0) - now_ms, 0)
                v = maxv - (((maxv - 1) * win - base_rel) // win + 1)
            else:
                v = 0 if now_ms >= expiry.get(slot, 0) else values.get(slot, 0)
            if v + delta > maxv:
                ok = False
                break
        if ok:
            for slot, delta, _maxv, win, is_bucket in hits:
                if is_bucket:
                    expiry[slot] = max(expiry.get(slot, 0), now_ms) + delta * win
                elif now_ms >= expiry.get(slot, 0):
                    values[slot] = delta
                    expiry[slot] = now_ms + win
                else:
                    values[slot] = values.get(slot, 0) + delta
        admitted.append(ok)
    return admitted


def test_full_width_every_bucket_vs_serial_oracle():
    """The serving shapes, all of them: a default deployment's table
    (2^20 slots + the scratch row, an odd length) driven at every pow2
    hit bucket the batcher can emit (8..8192) with both lanes live in
    one batch — contended hot slots, multi-counter requests that couple
    a window cell to a bucket cell, slots spread over the whole table —
    decided exactly as the serial oracle does, state carried (donated)
    from bucket to bucket. Then the off-path kernels once each at that
    width: read, top-k drain, clear, epoch rebase. The same test passes
    on one chip (JAX_PLATFORMS=tpu)."""
    capacity = 1 << 20
    rng = np.random.default_rng(21)
    state = K.make_table(capacity)
    values, expiry = {}, {}
    now_ms = 10_000
    hot = rng.integers(0, capacity // 2, 8) * 2  # even slots: windows
    H = 8
    while H <= 8192:
        batch, nhits = [], 0
        while nhits < H - 1:
            roll = rng.random()
            if roll < 0.3:      # contended hot window cell
                slot = int(rng.choice(hot))
            elif roll < 0.6:    # wide window cell (incl. the last row)
                slot = int(rng.integers(0, capacity // 2)) * 2
            else:               # bucket cell, 16 hot ones + wide
                slot = int(rng.integers(0, 16 if roll < 0.8
                                        else capacity // 2)) * 2 + 1
            hits = [(slot, 1, 3, 60_000, False) if slot % 2 == 0
                    else (slot, 1, 4, 250, True)]
            if rng.random() < 0.25:  # couple to one cell of the other lane
                other = int(rng.choice(hot)) if slot % 2 else 1
                hits.append((other, 1, 3, 60_000, False) if other % 2 == 0
                            else (other, 1, 4, 250, True))
            batch.append(hits)
            nhits += len(hits)
        assert _bucket(nhits) == H
        cols = [np.full(H, capacity, np.int32), np.zeros(H, np.int32),
                np.full(H, np.iinfo(np.int32).max, np.int32),
                np.zeros(H, np.int32), np.full(H, H - 1, np.int32)]
        bucket = np.zeros(H, bool)
        i = 0
        for r, hits in enumerate(batch):
            for slot, delta, maxv, win, is_bucket in hits:
                for col, v in zip(cols, (slot, delta, maxv, win, r)):
                    col[i] = v
                bucket[i] = is_bucket
                i += 1
        state, result = K.check_and_update_batch(
            state, *cols, np.zeros(H, bool), bucket, np.int32(now_ms))
        got = np.asarray(result.admitted)[: len(batch)]
        want = _serial_oracle_mixed(batch, values, expiry, now_ms)
        assert list(got) == want, f"bucket {H}"
        assert any(want) and (H < 256 or not all(want)), (
            f"bucket {H}: the batch exercised one verdict only")
        now_ms += 300  # a bucket token refills between batches
        H *= 2

    live = np.asarray(sorted(values), np.int32)[:4096]
    v, _ttl = K.read_slots(state, live, np.int32(now_ms))
    assert [int(x) for x in v] == [values[int(sl)] for sl in live]
    new_hits, counts, slots = K.drain_top_hits(state.hits, 64)
    assert int(counts[0]) > 0 and int(np.asarray(new_hits).sum()) == 0
    assert set(int(sl) for sl in np.asarray(slots)[:4]) <= (
        set(int(h) for h in hot) | set(range(1, 32, 2)))
    state = K.CounterTableState(state.values, state.expiry_ms, new_hits)
    state = K.clear_slots(state, live)
    v, _ttl = K.read_slots(state, live, np.int32(now_ms))
    assert not np.asarray(v).any()
    before = np.asarray(state.expiry_ms)
    state = K.rebase_epoch(state, np.int32(5_000))
    assert (np.asarray(state.expiry_ms) == np.maximum(before - 5_000, 0)).all()


def test_batch_multi_limit_coupling():
    """A request rejected by one counter must not consume from its other
    counters (all-or-nothing), freeing room for later requests."""
    # slot 0: max 1; slot 1: max 2.
    batch = [
        [(0, 1, 1, 60_000), (1, 1, 2, 60_000)],  # admitted (0->1, 1->1)
        [(0, 1, 1, 60_000), (1, 1, 2, 60_000)],  # rejected by slot 0
        [(1, 1, 2, 60_000)],                      # admitted (1->2): the
        # rejected request above must not have consumed slot 1
        [(1, 1, 2, 60_000)],                      # rejected (full)
    ]
    _state, got = _run_kernel(batch, capacity=8, now_ms=1000)
    assert list(got) == [True, False, True, False]


def test_kernel_window_reset_within_batch():
    """First admitted hit on an expired cell resets the window for the rest
    of the batch."""
    state = K.make_table(8)
    # Seed slot 3 with value 5, expired at t=500.
    batch0 = [[(3, 5, 100, 500)]]
    state, _ = _run_kernel(batch0, 8, now_ms=0, state=state)
    # At t=1000 the cell is expired; two hits with max 6: 5+1 would exceed if
    # the window had not reset; fresh window admits both (1, then 2).
    batch1 = [[(3, 1, 6, 60_000)], [(3, 1, 6, 60_000)]]
    state, got = _run_kernel(batch1, 8, now_ms=1000, state=state)
    assert list(got) == [True, True]
    v, ttl = K.read_slots(state, np.asarray([3], np.int32), np.int32(1000))
    assert int(v[0]) == 2
    assert int(ttl[0]) == 60_000


def test_long_window_limit_enforced_with_uptime():
    """Regression: windows near/beyond the int32-ms range used to wrap
    (now_ms + window overflow) and read as always-expired -> fail-open.
    A 30-day window with 1 hour of uptime must enforce exactly."""
    clock = FakeClock()
    storage = TpuStorage(capacity=64, clock=clock)
    limiter = RateLimiter(storage)
    limiter.add_limit(Limit("ns", 2, 30 * 24 * 3600))
    clock.advance(3600)  # 1 hour of process uptime before first hit
    from limitador_tpu.core.cel import Context
    results = [
        limiter.check_rate_limited_and_update("ns", Context({}), 1).limited
        for _ in range(4)
    ]
    assert results == [False, False, True, True]
    # Still enforced (window capped at ~12 days, not wrapped) much later.
    clock.advance(3600)
    assert limiter.check_rate_limited_and_update("ns", Context({}), 1).limited


def test_snapshot_restore_roundtrip(tmp_path):
    """Checkpoint/resume: the device table + key space survive a restart
    with values and absolute expiries intact."""
    clock = FakeClock()
    storage = TpuStorage(capacity=128, clock=clock)
    limiter = RateLimiter(storage)
    limit = Limit("ns", 10, 60, [], ["u"])
    limiter.add_limit(limit)
    limiter.update_counters("ns", Context({"u": "a"}), 7)
    clock.advance(5)

    path = str(tmp_path / "table.ckpt")
    storage.snapshot(path)

    restored = TpuStorage.restore(path, clock=clock)
    limiter2 = RateLimiter(restored)
    limiter2.add_limit(limit)
    counters = limiter2.get_counters("ns")
    assert len(counters) == 1
    c = next(iter(counters))
    assert c.remaining == 3
    assert abs(c.expires_in - 55) < 0.1  # absolute expiry preserved
    # counting resumes where it left off
    r = limiter2.check_rate_limited_and_update("ns", Context({"u": "a"}), 3)
    assert not r.limited
    assert limiter2.check_rate_limited_and_update(
        "ns", Context({"u": "a"}), 1).limited


def test_add_counter_on_recycled_slot_starts_clean():
    """r5 review follow-up: add_counter allocates WITHOUT a following
    kernel batch, so a slot recycled from an evicted/deleted counter
    must be cleared at allocation — otherwise the first (non-fresh)
    check reads the previous occupant's live cell."""
    clock = FakeClock()
    storage = TpuStorage(capacity=1 << 6, clock=clock)
    limiter = RateLimiter(storage)
    old = Limit("old", 10, 3600, [], [])
    limiter.add_limit(old)
    # occupy the simple slot with a near-full live window
    limiter.check_rate_limited_and_update("old", Context({}), 9)
    storage.delete_counters({old})
    # the freed slot is recycled for a NEW simple counter via
    # add_counter... (delete_counters clears; force the dirtier path by
    # evicting a qualified occupant instead)
    q = Limit("q", 10, 3600, [], ["u"])
    limiter.add_limit(q)
    for u in range(1 << 6):  # roll through the whole table, evicting
        limiter.check_rate_limited_and_update("q", Context({"u": str(u)}), 9)
    fresh = Limit("fresh", 10, 3600, [], [])
    limiter.add_limit(fresh)  # add_counter allocates a recycled slot
    # all 10 units are available on the brand-new counter
    got = [
        limiter.check_rate_limited_and_update("fresh", Context({}), 1).limited
        for _ in range(11)
    ]
    assert got == [False] * 10 + [True]
