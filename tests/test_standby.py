"""Warm standby & sub-second host join (ISSUE 18) — fast tier.

In-process miniature pods (InMemory-backed ``PodFrontend``s over real
gRPC peer lanes): the WarmStandby's kernel warm-up and debug surface,
a grow-mode ``join_host`` (the joiner answers forwards the moment the
commit lands, with the causal ``join_begin < epoch_bump < join_end``
chain), a replace-mode join (zero slices move, one epoch bump), the
plan-seed wire round trip (byte-identical plans; stale-epoch and
stale-limits discard), and the ``--standby off`` default pin (no
callbacks armed — construction byte-identical to PR 17). The
promotion-under-fire drill lives in tests/test_pod_join_drill.py
(`make pod-join-drill`).
"""

import asyncio
import socket
import subprocess
import sys
from pathlib import Path

import pytest

from limitador_tpu.routing import PodRouter, PodTopology

REPO_ROOT = Path(__file__).parent.parent


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# -- the in-process pod + standby harness --------------------------------------


def _standby_pod(n_members, limits=None, warm=False):
    """``n_members`` live pod members plus ONE memberless warm standby
    (the last index of every returned list): formed lane, provisional
    single-host router, resize coordinator with join callbacks armed —
    exactly the ``--standby on`` boot, minus the real server."""
    pytest.importorskip("grpc")
    from limitador_tpu import Limit, RateLimiter
    from limitador_tpu.server.peering import (
        PeerLane,
        PodFrontend,
        PodResilience,
    )
    from limitador_tpu.server.resize import PodResizeCoordinator
    from limitador_tpu.server.standby import WarmStandby
    from limitador_tpu.storage.in_memory import InMemoryStorage

    limits = limits or [
        Limit("join", 50, 300, [], ["u"], name="per_u")
    ]
    n_total = n_members + 1
    ports = [_free_port() for _ in range(n_total)]
    addrs = [f"127.0.0.1:{ports[h]}" for h in range(n_total)]
    lanes, fronts = [], []
    for host in range(n_total):
        member = host < n_members
        cfg = PodResilience(
            degraded=True, retry=True, breaker_failures=2,
            breaker_reset_s=0.2, probe_interval_s=0.1,
            retry_backoff_ms=1.0,
        )
        lane = PeerLane(
            host if member else 0, addrs[host],
            {
                o: addrs[o] for o in range(n_members)
                if member and o != host
            },
            None, resilience=cfg,
        )
        lane.start()
        front = PodFrontend(
            RateLimiter(InMemoryStorage(4096)),
            PodRouter(PodTopology(
                hosts=n_members if member else 1,
                host_id=host if member else 0,
                shards_per_host=1,
            )),
            lane, resilience=cfg,
        )
        coordinator = PodResizeCoordinator(
            front,
            peers=(
                {h: addrs[h] for h in range(n_members)}
                if member else {}
            ),
            listen_address=addrs[host],
        )
        front.attach_resize(coordinator)
        if member:
            asyncio.run(front.configure_with(limits))
        lanes.append(lane)
        fronts.append(front)
    standby = WarmStandby(
        fronts[-1], fronts[-1].resize, warm_buckets=(8,)
    )
    if warm:
        standby.warm()
    return lanes, fronts, standby, addrs, limits


def _check(front, user, ns="join", delta=1):
    from limitador_tpu import Context

    return asyncio.run(front.check_rate_limited_and_update(
        ns, Context({"u": user}), delta, False
    ))


def _stop(lanes):
    for lane in lanes:
        lane.stop()


def _owned_users(front, owner, limits, n=3, ns="join"):
    out = []
    i = 0
    while len(out) < n:
        user = f"owned-{owner}-{i}"
        key = (limits[0]._identity, (("u", user),))
        if front.router.topology.owner_host(key) == owner:
            out.append(user)
        i += 1
        assert i < 10000
    return out


# -- the warm standby ----------------------------------------------------------


def test_warm_standby_compiles_kernels_and_reports():
    lanes, fronts, standby, _addrs, _limits = _standby_pod(2)
    try:
        assert not standby.ready
        out = standby.warm()
        assert out["ready"] and standby.ready
        # two jitted entry points per pow2 bucket
        assert standby.warm_kernels == 2 * len(standby.warm_buckets)
        stats = standby.stats()
        assert stats["standby_ready"] == 1
        assert stats["standby_warm_kernels"] == standby.warm_kernels
        assert stats["standby_warm_seconds"] > 0
        # the standby_* families flow through library_stats
        lib = fronts[-1].library_stats()
        assert lib["standby_ready"] == 1
        status = standby.status()
        assert status["buckets"] == [8]
        assert status["table_capacity"] > 0
        assert status["join_ttfd_seconds"] == 0.0
        # the boot emitted the typed event
        kinds = [
            e["kind"] for e in fronts[-1].events_debug()["events"]
        ]
        assert "standby_ready" in kinds
        # the debug surface: armed on the standby, 404-shaped elsewhere
        assert fronts[-1].standby_debug()["armed"]
        assert fronts[0].standby_debug() == {"armed": False}
    finally:
        _stop(lanes)


def test_warm_failure_degrades_but_stays_joinable(monkeypatch):
    lanes, _fronts, standby, _addrs, _limits = _standby_pod(2)
    try:
        monkeypatch.setattr(
            standby, "_compile_buckets",
            lambda: (_ for _ in ()).throw(RuntimeError("no backend")),
        )
        out = standby.warm()
        # degraded to cold-compile-on-first-miss, never unjoinable
        assert out["ready"] and standby.ready
        assert standby.warm_kernels == 0
    finally:
        _stop(lanes)


# -- grow-mode join ------------------------------------------------------------


def test_join_grow_answers_forwards_with_causal_chain():
    lanes, fronts, standby, addrs, limits = _standby_pod(
        2, warm=True
    )
    try:
        for i in range(8):
            _check(fronts[i % 2], f"pre-{i}")
        out = fronts[0].resize.join_host(addrs[-1])
        assert out["ok"], out
        assert out["mode"] == "grow" and out["joiner"] == 2
        assert out["join_seconds"] > 0
        # pod-wide adoption: the standby is host 2 of a 3-host pod
        assert fronts[-1].router.topology.hosts == 3
        assert fronts[-1].router.topology.host_id == 2
        assert {f.router.topology_epoch for f in fronts} == {
            fronts[0].router.topology_epoch
        }
        # the joiner answers decisions for its shard range, forwarded
        # from an old member — and the first one stamps ttfd
        for user in _owned_users(fronts[0], 2, limits):
            got = _check(fronts[0], user)
            assert got is not None
        stats = fronts[-1].resize.stats()
        assert stats["join_ttfd_seconds"] > 0
        # the initiator's causal chain: the joiner was configured and
        # seeded BEFORE the epoch flip, and the join brackets the bump
        seq = {}
        for event in fronts[0].events_debug()["events"]:
            seq.setdefault(event["kind"], event["seq"])
        assert (
            seq["join_begin"] < seq["epoch_bump"] < seq["join_end"]
        ), seq
        istats = fronts[0].resize.stats()
        assert istats["join_completed"] == 1
        assert istats["join_aborted"] == 0
        assert istats["join_seconds"] > 0
    finally:
        _stop(lanes)


def test_join_replace_dead_member_zero_slices_moved():
    lanes, fronts, _standby, addrs, limits = _standby_pod(
        3, warm=True
    )
    try:
        for i in range(8):
            _check(fronts[i % 3], f"pre-{i}")
        epoch_before = fronts[0].router.topology_epoch
        # SIGKILL stand-in: host 1 stops serving its lane
        lanes[1].stop()
        out = fronts[0].resize.join_host(addrs[-1], replace=1)
        assert out["ok"], out
        assert out["mode"] == "replace" and out["joiner"] == 1
        # same geometry, one epoch bump, ZERO slices moved
        assert fronts[0].router.topology.hosts == 3
        assert fronts[0].router.topology_epoch == epoch_before + 1
        assert out["transition"]["moved_slices"] == 0
        # the standby took over the dead id and answers its keys
        assert fronts[-1].router.topology.host_id == 1
        for user in _owned_users(fronts[0], 1, limits):
            assert _check(fronts[0], user) is not None
        seq = {}
        for event in fronts[0].events_debug()["events"]:
            seq.setdefault(event["kind"], event["seq"])
        assert seq["join_begin"] < seq["epoch_bump"] < seq["join_end"]
        assert "migrate_begin" not in seq
        assert fronts[0].resize.stats()["join_completed"] == 1
    finally:
        _stop(lanes)


def test_join_validates_replace_target():
    lanes, fronts, _standby, addrs, _limits = _standby_pod(2)
    try:
        with pytest.raises(ValueError, match="outside"):
            fronts[0].resize.join_host(addrs[-1], replace=5)
        with pytest.raises(ValueError, match="itself"):
            fronts[0].resize.join_host(addrs[-1], replace=0)
        # failed validation never counts a join attempt
        assert fronts[0].resize.stats()["join_completed"] == 0
    finally:
        _stop(lanes)


# -- the shipped plan-cache seed -----------------------------------------------


def test_plan_wire_round_trip_byte_identical():
    """A seed row rebuilds the EXACT plan: same blob, same kind/delta/
    names, and — with the importer resolving each counter to the same
    slot — the identical record tuple."""
    from limitador_tpu import Limit
    from limitador_tpu.core.counter import Counter
    from limitador_tpu.tpu.plan_cache import (
        PLAN_KERNEL,
        PLAN_OK,
        DecisionPlan,
        plan_from_wire,
        plan_to_wire,
    )

    limit = Limit("seed", 9, 60, [], ["u"], name="per_u")
    counter = Counter(limit, {"u": "alice"})
    trivial = DecisionPlan(PLAN_OK, namespace="seed", delta=2)
    wire = plan_to_wire(b"blob-ok", trivial)
    blob, rebuilt = plan_from_wire(wire)
    assert blob == b"blob-ok"
    assert (rebuilt.kind, rebuilt.namespace, rebuilt.delta) == (
        PLAN_OK, "seed", 2,
    )

    kernel = DecisionPlan(
        PLAN_KERNEL, namespace="seed", delta=1,
        record=(7, 9, 60000, 0), limit_names=("per_u",), slots=(7,),
    )
    wire = plan_to_wire(
        b"blob-k", kernel, counter_of_slot={7: counter}.get
    )
    assert wire["hits"][0]["c"]["ns"] == "seed"
    blob, rebuilt = plan_from_wire(
        wire, slot_of_counter=lambda c: 7
    )
    assert blob == b"blob-k"
    assert rebuilt.record == kernel.record
    assert rebuilt.slots == kernel.slots
    assert rebuilt.limit_names == kernel.limit_names
    # an unattributable kernel hit (recycled slot) never travels
    assert plan_to_wire(
        b"blob-k", kernel, counter_of_slot={}.get
    ) is None
    # and an unresolvable one never mis-seeds
    assert plan_from_wire(wire, slot_of_counter=lambda c: None) is None


def test_plan_seed_export_import_round_trip_and_stale_epoch():
    """import_seed rides put(): a full cache round-trips entry-exact,
    and a limits reload racing the ship (epoch bump between export and
    import) discards the WHOLE seed — the stale-put contract."""
    from limitador_tpu.tpu.plan_cache import (
        PLAN_OK,
        DecisionPlan,
        DecisionPlanCache,
    )

    donor = DecisionPlanCache(64)
    for i in range(5):
        donor.put(
            f"blob-{i}".encode(),
            DecisionPlan(PLAN_OK, namespace=f"ns{i}", delta=i + 1),
        )
    seed = donor.export_seed()
    assert len(seed) == 5

    joiner = DecisionPlanCache(64)
    assert joiner.import_seed(seed) == 5
    assert sorted(joiner.entries) == sorted(donor.entries)
    for blob, plan in donor.entries.items():
        got = joiner.entries[blob]
        assert (got.namespace, got.delta) == (plan.namespace, plan.delta)

    # the race: limits reload on the joiner AFTER the donor exported
    racing = DecisionPlanCache(64)
    shipped_epoch = racing.epoch
    racing.bump_epoch()
    assert racing.import_seed(seed, epoch=shipped_epoch) == 0
    assert len(racing) == 0


def test_plan_seed_stale_limits_fingerprint_discards_whole_seed():
    """The cross-process half of the contract: a seed stamped under a
    different limits generation discards whole on the joiner."""
    lanes, fronts, _standby, _addrs, _limits = _standby_pod(2)
    try:
        # InMemory frontends attach no plan cache: export is the empty
        # seed, import refuses — the ship treats both as non-fatal
        seed = fronts[0].plan_seed_export()
        assert seed["entries"] == []
        assert seed["limits_fp"] == fronts[1]._limits_fingerprint()
        out = fronts[1].plan_seed_import(seed)
        assert not out["ok"] and "no plan cache" in out["error"]
        # fingerprints move with the limits generation
        from limitador_tpu import Limit

        asyncio.run(fronts[1].configure_with([
            Limit("join", 99, 300, [], ["u"], name="per_u")
        ]))
        assert seed["limits_fp"] != fronts[1]._limits_fingerprint()
    finally:
        _stop(lanes)


def test_plan_seed_stale_fingerprint_on_real_cache(monkeypatch):
    """With a plan cache attached, a mismatched fingerprint returns
    ``stale_limits`` without touching the cache."""
    from limitador_tpu.tpu.plan_cache import DecisionPlanCache

    lanes, fronts, _standby, _addrs, _limits = _standby_pod(2)
    try:
        class _Pipe:
            plan_cache = DecisionPlanCache(16)
            storage = None

        monkeypatch.setattr(fronts[1], "pipeline", _Pipe())
        out = fronts[1].plan_seed_import(
            {"entries": [{"bad": 1}], "limits_fp": "0" * 16}
        )
        assert out["ok"] and out["seeded"] == 0
        assert out["stale_limits"]
        assert len(_Pipe.plan_cache) == 0
        kinds = [
            e["kind"] for e in fronts[1].events_debug()["events"]
        ]
        assert "plan_seeded" in kinds
    finally:
        _stop(lanes)


# -- the off-by-default pin ----------------------------------------------------


def test_standby_off_default_and_unarmed_pin():
    """``--standby off`` (the default) is PR 17 byte-identical: no
    WarmStandby constructed, no join/plan-seed callbacks armed on the
    lane, no ``standby_*`` keys in library_stats."""
    from limitador_tpu.server.__main__ import build_parser

    default = build_parser().parse_args(["limits.yaml", "memory"])
    assert default.standby == "off"
    on = build_parser().parse_args(
        ["limits.yaml", "tpu", "--standby", "on"]
    )
    assert on.standby == "on"

    pytest.importorskip("grpc")
    from limitador_tpu import Limit, RateLimiter
    from limitador_tpu.server.peering import PeerLane, PodFrontend
    from limitador_tpu.storage.in_memory import InMemoryStorage

    lane = PeerLane(
        0, f"127.0.0.1:{_free_port()}", {}, None
    )
    front = PodFrontend(
        RateLimiter(InMemoryStorage(256)),
        PodRouter(PodTopology(hosts=1, host_id=0, shards_per_host=1)),
        lane,
    )
    assert front.standby is None
    assert lane.join_cb is None
    assert lane.plan_seed_cb is None
    assert front.standby_debug() == {"armed": False}
    asyncio.run(front.configure_with(
        [Limit("pin", 5, 60, [], ["u"], name="n")]
    ))
    assert "standby_ready" not in front.library_stats()


# -- registries, events, metrics -----------------------------------------------


def test_join_event_kinds_registered():
    from limitador_tpu.observability.events import EVENT_KINDS

    for kind in (
        "join_begin", "join_end", "standby_ready", "plan_seeded",
    ):
        assert kind in EVENT_KINDS


def test_registry_owns_join_and_standby_prefixes():
    from limitador_tpu.server.resize import (
        METRIC_FAMILIES as RESIZE_FAMILIES,
    )
    from limitador_tpu.server.standby import (
        METRIC_FAMILIES as STANDBY_FAMILIES,
    )
    from limitador_tpu.tools.analysis.registries import (
        REGISTRY_OWNED_PREFIXES,
    )

    assert (
        REGISTRY_OWNED_PREFIXES["join_"]
        == "limitador_tpu/server/resize.py"
    )
    assert (
        REGISTRY_OWNED_PREFIXES["standby_"]
        == "limitador_tpu/server/standby.py"
    )
    for family in (
        "join_completed", "join_aborted", "join_seconds",
        "join_seed_entries", "join_ttfd_seconds",
    ):
        assert family in RESIZE_FAMILIES
    for family in (
        "standby_ready", "standby_warm_kernels", "standby_warm_seconds",
    ):
        assert family in STANDBY_FAMILIES


def test_join_metric_families_render():
    """Every join_*/standby_* family declared and polled off
    library_stats into the exposition."""
    from limitador_tpu.observability import PrometheusMetrics

    class Source:
        def library_stats(self):
            return {
                "join_completed": 2, "join_aborted": 1,
                "join_seconds": 0.42, "join_seed_entries": 17,
                "join_ttfd_seconds": 0.031,
                "standby_ready": 1, "standby_warm_kernels": 14,
                "standby_warm_seconds": 1.9,
            }

    metrics = PrometheusMetrics()
    metrics.attach_library_source(Source())
    text = metrics.render().decode()
    assert "join_completed_total 2.0" in text
    assert "join_aborted_total 1.0" in text
    assert "join_seconds_total 0.42" in text
    assert "join_seed_entries_total 17.0" in text
    assert "join_ttfd_seconds 0.031" in text
    assert "standby_ready 1.0" in text
    assert "standby_warm_kernels 14.0" in text
    assert "standby_warm_seconds 1.9" in text


def test_flight_recorder_has_join_lane():
    from limitador_tpu.observability.flight import FLIGHT_LANES

    assert "join" in FLIGHT_LANES


# -- the persistent XLA cache (JAX_COMPILATION_CACHE_DIR, slow) ----------------

_XLA_WARM_SNIPPET = """
import os, sys, time
import jax
from limitador_tpu.device import enable_compile_cache
assert enable_compile_cache() == os.environ["JAX_COMPILATION_CACHE_DIR"]
from limitador_tpu.ops import kernel as K
import jax.numpy as jnp
import numpy as np
t0 = time.perf_counter()
state = K.make_table(64)
H = 8
slots = jnp.full((H,), 64, jnp.int32)
zeros = jnp.zeros((H,), jnp.int32)
maxes = jnp.full((H,), np.iinfo(np.int32).max, jnp.int32)
windows = jnp.ones((H,), jnp.int32)
off = jnp.zeros((H,), bool)
state, result = K.check_and_update_batch(
    state, slots, zeros, maxes, windows, zeros, off, off, jnp.int32(0)
)
jax.block_until_ready(result.admitted)
print(round(time.perf_counter() - t0, 4))
"""


@pytest.mark.slow
def test_compile_cache_persists_kernel_compiles(tmp_path):
    """A SECOND process warming the same kernels hits the persistent
    cache the environment placed — the compiled programs are on disk
    after the first boot and no new cache entries are written by the
    re-warm."""
    cache_dir = tmp_path / "xla"
    cache_dir.mkdir()

    def run():
        proc = subprocess.run(
            [sys.executable, "-c", _XLA_WARM_SNIPPET],
            capture_output=True, text=True, timeout=300,
            env={
                "PYTHONPATH": str(REPO_ROOT),
                "PATH": "/usr/bin:/bin:/usr/local/bin",
                "JAX_PLATFORMS": "cpu",
                "JAX_COMPILATION_CACHE_DIR": str(cache_dir),
                "HOME": str(tmp_path),
            },
            cwd=str(REPO_ROOT),
        )
        assert proc.returncode == 0, proc.stderr[-1000:]
        return float(proc.stdout.strip().splitlines()[-1])

    run()
    cache_files = {
        p.name for p in cache_dir.iterdir() if p.name.endswith("-cache")
    }
    if not cache_files:
        pytest.skip("backend does not persist compiled programs")
    run()
    after = {
        p.name for p in cache_dir.iterdir() if p.name.endswith("-cache")
    }
    # the second warm-up compiled NOTHING new: every program was served
    # from the persistent cache the first boot wrote
    assert after == cache_files
