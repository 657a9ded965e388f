"""Benchmark: ShouldRateLimit decisions/sec on the device counter table.

Default run reproduces BASELINE.md config 4 — 1M hot keys, Zipf-0.99,
32k-request micro-batches, per-key fixed-window limits — against the
north-star target of 10M decisions/sec (BASELINE.json) and prints ONE JSON
line:

    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

vs_baseline is value / 10M (the target the driver tracks). Human-readable
details (latency percentiles, config) go to stderr.

Every config that wants the chip fails when jax finds none (exit 3, no
JSON row): a CPU number is never recorded under a device metric's name.
Rows that are CPU by declaration (memory, pod, the matrix's eight-device
virtual mesh for sharded) say so in the row.

The other BASELINE configs run with --config:
    --config memory     in-memory oracle, 1k keys (CPU baseline, config 1)
    --config pipeline   full compiled pipeline: descriptor replay, 100k
                        keys, 1 limit/namespace (config 2)
    --config tenants    10k namespaces x 100 keys, mixed windows (config 3)
    --config lease      quota-lease tier on vs off, interleaved in one
                        process over a Zipf drive: lease_engine_speedup /
                        lease_serving_speedup + leased-hit p50/p99 ns
    --config native     native columnar serving path, hot lane on vs off
    --config device     1M keys Zipf-0.99, 32k micro-batches (config 4,
                        the default headline)
    --config sharded    keys sharded over all devices, psum global region
                        (config 5; multi-chip on a virtual mesh off-TPU)
    --config grpc       closed-loop ShouldRateLimit over a real socket:
                        p50/p99 vs the 2ms target (also rides along with
                        the default device run as grpc_* fields)
    --config fleet      N replica processes sharing one RLS port via
                        SO_REUSEPORT over one network authority (the
                        N-limitadors-one-Redis topology)
    --config pod        1/2/4-process jax.distributed CPU pods on this
                        box: summed owned-key device-lane throughput,
                        pod_scaling_efficiency, the routed-ingress
                        local/forwarded split (round-robin AND ring-hash
                        arrivals) with the peer hop's p99, and the
                        shard-aware native hot lane's per-host engine
                        rate / local-foreign split / bulk-forward sizes,
                        plus the elastic-pod resize row (decisions/sec
                        and p99 before/during/after a live 2->4 resize
                        with pod_resize_seconds and the routed-share
                        recovery clock)
    --config backends   reference criterion scenarios per backend
    --config flight     flight recorder on vs off: tap nanosecond cost
                        across a sample-stride sweep + in-memory
                        decisions/s with the recorder attached/detached
    --config controller self-driving capacity A/B (ISSUE 20): one
                        open-loop bursty multi-tenant drive (zipf-mixture
                        tenants, calm -> 5x load step -> diurnal ramp ->
                        night) through the REAL admission plane, static
                        vs adaptive (live CapacityController): the
                        adaptive row must hold SLO burn < 1 through the
                        step that makes static shed blindly, calm no
                        worse; plus the autoscale segment — the same
                        drive with the membership axis armed (grow on
                        sustained burn, drain on sustained idle, flap
                        count through the ramp)
"""

import argparse
import json
import sys
import time

import numpy as np


def zipf_keys(n_keys: int, n_samples: int, s: float, rng) -> np.ndarray:
    """Bounded Zipf(s) over [0, n_keys) by inverse-CDF over rank weights."""
    w = 1.0 / np.arange(1, n_keys + 1, dtype=np.float64) ** s
    cdf = np.cumsum(w)
    u = rng.random(n_samples) * cdf[-1]
    return np.searchsorted(cdf, u).astype(np.int32)


_BOX_CALIBRATION = None


def box_calibration_score() -> float:
    """Fixed single-thread spin + memcpy workload, scored in passes per
    second (higher = faster box). Recorded on every BENCH row because
    absolute throughput numbers are only comparable across rounds after
    normalizing by box speed — the r4 box swung ~6x mid-round, making
    raw absolutes uninterpretable. Performance CLAIMS (e.g. the hot-lane
    speedup) therefore ride same-process on/off ratios; this score is
    the cross-round normalizer for everything else."""
    global _BOX_CALIBRATION
    if _BOX_CALIBRATION is None:
        src = bytes(4 << 20)
        dst = bytearray(4 << 20)
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            acc = 0
            for i in range(200_000):  # fixed Python-interpreter spin
                acc += i ^ (acc & 0xFF)
            for _ in range(24):  # 96 MB of memcpy
                dst[:] = src
            best = min(best, time.perf_counter() - t0)
        _BOX_CALIBRATION = round(1.0 / best, 3)
    return _BOX_CALIBRATION


def device_backed() -> bool:
    """Is a non-CPU jax backend serving THIS process? Tagged onto every
    BENCH row. Asked of the parent's own backend: a chip belongs to one
    process, so a probe child started after the parent's jax work could
    never have it. A process that never imported jax (memory, pod: CPU
    rows by declaration) answers False without importing it."""
    from limitador_tpu.observability.native_plane import (
        device_backed_runtime,
    )

    return bool(device_backed_runtime())


def require_chip() -> None:
    """Gate for every in-process config that wants the chip: place the
    compile cache, then fail the run (exit 3, no JSON row) when jax
    found no accelerator — asked for by name or not, a CPU number never
    goes under a device metric's name."""
    import jax

    from limitador_tpu.device import enable_compile_cache

    enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        print(
            "ERROR: jax found no accelerator (platform cpu); this config "
            "measures the chip and records no CPU number in its place",
            file=sys.stderr,
        )
        sys.exit(3)


_ANALYSIS_CLEAN = None


def analysis_clean() -> bool:
    """One in-process run of the static-analysis gate (ISSUE 9),
    cached for the bench process. Recorded on every BENCH row so a
    round captured from a dirty tree (parked baseline entries, local
    hacks) is machine-distinguishable from a gate-green one."""
    global _ANALYSIS_CLEAN
    if _ANALYSIS_CLEAN is None:
        try:
            from limitador_tpu.tools.analysis import repo_root, run_passes

            active, _suppressed = run_passes(repo_root())
            _ANALYSIS_CLEAN = not active
        except Exception:
            _ANALYSIS_CLEAN = False
    return _ANALYSIS_CLEAN


def sanitizer_variant_tag() -> str:
    """The active TPU_NATIVE_SANITIZE variant ("" = plain -O2 build).
    A sanitizer-instrumented native plane runs 2-20x slower — rows
    from such runs must never be read as device-round numbers."""
    from limitador_tpu.native.build import sanitizer_variant

    return sanitizer_variant() or ""


def serving_model_fit() -> dict:
    """The live online serving-model fit (ISSUE 14) at row-emit time:
    the process estimator is fed by every DeviceStatsRecorder the
    bench's drives construct (observability/model.py), so forcing one
    refit here yields the coefficients the row's traffic actually
    trained. Returns the compact ``fit_row()`` summary — coefficients +
    prequential R² + drift state + calibration — or ``{}`` when the fit
    is disabled (TPU_MODEL_FIT=off) or saw no device launches (host-only
    configs). Rows become cross-comparable by MODEL rather than by raw
    absolutes: two rounds on different box phases agree on the
    normalized coefficients even when every raw rate differs 2-6x."""
    try:
        from limitador_tpu.observability.model import (
            model_fit_enabled, process_estimator,
        )

        if not model_fit_enabled():
            return {}
        est = process_estimator()
        est.refit(force=True)
        if not est.observations:
            return {}
        return est.fit_row()
    except Exception:
        return {}


def emit(metric: str, value: float, unit: str, baseline: float,
         ndigits: int = 1, lower_is_better: bool = False, **extra) -> None:
    """One JSON result line. ``vs_baseline`` is uniformly >1-is-better:
    value/baseline for throughput rows, baseline/value when
    ``lower_is_better`` (latency targets). Every row carries the box
    calibration score (see ``box_calibration_score``), the process's
    own ``device_backed`` bit, the ``analysis_clean`` gate bit,
    the active ``sanitizer`` variant (ISSUE 9 bench hygiene) and the
    live ``serving_model`` fit (ISSUE 14 — coefficients + R², see
    ``serving_model_fit``)."""
    ratio = (baseline / value) if lower_is_better else (value / baseline)
    payload = {
        "metric": metric,
        "value": round(value, ndigits),
        "unit": unit,
        "vs_baseline": round(ratio, 4),
    }
    payload.update(extra)
    payload.setdefault("box_calibration_score", box_calibration_score())
    payload.setdefault("device_backed", device_backed())
    payload.setdefault("analysis_clean", analysis_clean())
    payload.setdefault("sanitizer", sanitizer_variant_tag())
    payload.setdefault("serving_model", serving_model_fit())
    print(json.dumps(payload))


def bench_memory():
    """Config 1: single-namespace fixed-window, 1k keys, in-memory oracle."""
    from limitador_tpu import Context, Limit, RateLimiter

    limiter = RateLimiter()
    limiter.add_limit(Limit("ns", 10**9, 60, [], ["u"]))
    users = [str(i) for i in range(1000)]
    ctxs = [Context({"u": u}) for u in users]
    n = 50_000
    t0 = time.perf_counter()
    for i in range(n):
        limiter.check_rate_limited_and_update("ns", ctxs[i % 1000], 1)
    dt = time.perf_counter() - t0
    print(f"memory oracle: {n/dt/1e3:.1f}k decisions/s", file=sys.stderr)
    emit("inmemory_decisions_per_sec", n / dt, "decisions/s", 1e7)


def bench_flight():
    """ISSUE 16: the flight recorder's hot-path cost, on vs off. Three
    evidence shapes: (a) the in-memory serving loop's decisions/s with
    the recorder tapping every decision vs detached (the end-to-end
    overhead at the default stride), (b) the raw ``tap()`` nanosecond
    cost across a sample-stride sweep (1 = ring every decision, up to
    256), and (c) the sampled-exemplar count each stride retains so the
    cost rows carry their coverage."""
    from limitador_tpu import Context, Limit, RateLimiter
    from limitador_tpu.observability.flight import FlightRecorder

    limiter = RateLimiter()
    limiter.add_limit(Limit("ns", 10**9, 60, [], ["u"]))
    ctxs = [Context({"u": str(i)}) for i in range(1000)]
    n = 50_000

    def serving_loop(tap):
        t0 = time.perf_counter()
        for i in range(n):
            d0 = time.perf_counter()
            limiter.check_rate_limited_and_update(
                "ns", ctxs[i % 1000], 1
            )
            if tap is not None:
                tap.tap(time.perf_counter() - d0, "lean", namespace="ns")
        return n / (time.perf_counter() - t0)

    off = serving_loop(None)
    recorder = FlightRecorder(sample_stride=64)
    on = serving_loop(recorder)
    overhead_pct = (off / on - 1.0) * 100.0 if on > 0 else 0.0
    print(
        f"flight recorder: {off/1e3:.1f}k decisions/s off, "
        f"{on/1e3:.1f}k on (stride 64, {recorder.exemplars} exemplars "
        f"ringed, overhead {overhead_pct:.2f}%)",
        file=sys.stderr,
    )
    emit(
        "flight_decisions_per_sec", on, "decisions/s", 1e7,
        recorder="on", sample_stride=64,
        decisions_per_sec_off=round(off, 1),
        overhead_pct=round(overhead_pct, 3),
    )
    m = 200_000
    for stride in (1, 16, 64, 256):
        rec = FlightRecorder(sample_stride=stride)
        t0 = time.perf_counter()
        for _ in range(m):
            rec.tap(1e-4, "lean")
        tap_ns = (time.perf_counter() - t0) / m * 1e9
        print(
            f"flight tap @ stride {stride}: {tap_ns:.0f}ns "
            f"({rec.exemplars} exemplars)",
            file=sys.stderr,
        )
        emit(
            "flight_tap_ns", tap_ns, "ns", 1000.0, ndigits=1,
            lower_is_better=True, sample_stride=stride,
            exemplars=rec.exemplars, tail_retained=rec.tail_retained,
        )


def controller_drive(rng, tenants=48, base=60.0, step_factor=5.0,
                     calm_ticks=150, step_ticks=150, ramp_ticks=300,
                     night_ticks=100):
    """The open-loop bursty multi-tenant drive (ISSUE 20): per-tick
    Poisson arrival counts over a zipf-mixture tenant population,
    through four segments — calm, a hard ``step_factor``x load step,
    one full diurnal ramp cycle (base -> peak -> base), night idle.
    Open loop on purpose: arrivals never slow down because the server
    sheds, which is exactly the regime that separates a capacity
    controller from reactive AIMD alone. Yields ``(tick, phase,
    [(namespace, count), ...])``; reused by the A/B row and the
    autoscale segment of ``--config controller``."""
    import math

    weights = 1.0 / np.arange(1, tenants + 1) ** 0.99
    weights /= weights.sum()
    names = [f"tenant-{i:02d}" for i in range(tenants)]
    total = calm_ticks + step_ticks + ramp_ticks + night_ticks
    for t in range(total):
        if t < calm_ticks:
            phase, rate = "calm", base
        elif t < calm_ticks + step_ticks:
            phase, rate = "step", base * step_factor
        elif t < calm_ticks + step_ticks + ramp_ticks:
            u = (t - calm_ticks - step_ticks) / ramp_ticks
            phase = "ramp"
            rate = base * (1.0 + (step_factor - 1.0) * 0.5
                           * (1.0 - math.cos(2.0 * math.pi * u)))
        else:
            phase, rate = "night", base * 0.2
        n = int(rng.poisson(rate))
        if n:
            counts = rng.multinomial(n, weights)
            arrivals = [
                (names[i], int(c)) for i, c in enumerate(counts) if c
            ]
        else:
            arrivals = []
        yield t, phase, arrivals


def _controller_sim(mode, seed=7):
    """One pass of ``controller_drive`` against the REAL admission
    plane — AdaptiveLimiter AIMD, priority shares, shed floor — over a
    simulated service stage: ``per_host_capacity`` decisions per 100ms
    tick per host, FIFO queue, per-decision queue wait judged against
    a 100ms budget (the sim plane's SLO). Modes:

    * ``static``   — the pre-controller plane: AIMD alone.
    * ``adaptive`` — a live CapacityController (admission knobs) holds
      the ceiling at the model's sustainable point instead of letting
      the AIMD envelope ride at the hard max until the step hits.
    * ``autoscale``— membership axis only: the controller grows a
      simulated 2-host pod on sustained burn (capacity scales with
      hosts) and drains it back on sustained night idle.

    All clocks (AIMD, admission, controller) run on simulated time, so
    the pass is deterministic for a seed."""
    from limitador_tpu.admission.controller import (
        AdmissionController,
        AdmissionShed,
    )
    from limitador_tpu.admission.overload import AdaptiveLimiter
    from limitador_tpu.admission.priority import PriorityResolver
    from limitador_tpu.control import (
        CapacityController,
        ModelPolicy,
        ServerActuator,
    )
    from limitador_tpu.observability.signals import ControlSignals

    tick_s = 0.1
    budget_s = 0.1        # the sim plane's SLO budget (one tick)
    slo_target = 0.01     # <= 1% of served decisions over budget
    per_host = 40         # served decisions per tick per host
    tenants = 48
    rng = np.random.default_rng(seed)

    class Clock:
        t = 0.0

        def __call__(self):
            return self.t

    clock = Clock()
    resolver = PriorityResolver(namespace_map={
        f"tenant-{i:02d}": i % 4 for i in range(tenants)
    })
    overload = AdaptiveLimiter(
        max_inflight=4096, target_queue_wait=0.05, clock=clock,
    )
    admission = AdmissionController(
        mode="enforce", overload=overload, priorities=resolver,
        clock=clock,
    )

    coordinator = None
    controller = None
    actuator = None
    offered_ewma = 0.0
    wait_ms = 0.0

    def capacity_tick():
        hosts = (
            coordinator.router.topology.hosts
            if coordinator is not None else 2
        )
        return per_host * hosts

    import types

    if mode == "adaptive":
        # the fitted serving model at this sim's operating point: the
        # Little's-law ceiling (max rate x budget) is what lets the
        # adaptive row hold the queue INSIDE the budget before the
        # step lands, instead of reacting after it blows
        estimator = types.SimpleNamespace(
            budget_ms=budget_s * 1e3,
            what_if=lambda: {
                "max_decisions_per_sec": capacity_tick() / tick_s,
                "predicted_decisions_per_sec": min(
                    offered_ewma, capacity_tick()
                ) / tick_s,
                "predicted_latency_ms": wait_ms,
            },
        )
        actuator = ServerActuator(overload=overload, admission=admission)
        # default ceiling margin (1.5x the Little's-law point): queue
        # depth caps at 120 < 2 service quanta, so every admitted
        # decision still lands inside the one-tick budget, while calm
        # traffic clears the priority-share caps untouched
        controller = CapacityController(
            actuator,
            policy=ModelPolicy(budget_ms=budget_s * 1e3),
            estimator=estimator, mode="on", interval_s=tick_s,
            clock=clock,
        )
    elif mode == "autoscale":
        coordinator = types.SimpleNamespace(
            busy=False,
            _peers={0: "sim-0", 1: "sim-1"},
            router=types.SimpleNamespace(
                topology=types.SimpleNamespace(hosts=2)
            ),
        )

        def _join(address):
            h = coordinator.router.topology.hosts
            coordinator._peers[h] = address
            coordinator.router.topology.hosts = h + 1
            return {"ok": True, "mode": "grow", "joiner": h}

        def _drain():
            h = coordinator.router.topology.hosts
            coordinator._peers.pop(h - 1, None)
            coordinator.router.topology.hosts = h - 1
            return {"ok": True}

        coordinator.join_host = _join
        coordinator.drain_host = _drain
        actuator = ServerActuator(
            coordinator=coordinator, standby_addresses=["sim-standby"],
            min_hosts=2, max_hosts=3,
        )
        controller = CapacityController(
            actuator, policy=ModelPolicy(budget_ms=budget_s * 1e3),
            mode="on", interval_s=tick_s, sustain_s=1.0, dwell_s=5.0,
            clock=clock,
        )

    from collections import deque as _deque

    queue = []                       # (enqueue_tick, ticket) FIFO
    window = _deque(maxlen=20)       # (served, over) per tick
    burn = 0.0
    prev_counts = {}
    phases = {}
    phase_order = []
    for t, phase, arrivals in controller_drive(rng, tenants=tenants):
        clock.t = t * tick_s
        if phase not in phases:
            phase_order.append(phase)
        agg = phases.setdefault(phase, {
            "ticks": 0, "offered": 0, "served": 0, "over": 0,
            "max_burn": 0.0, "sheds": {},
        })
        agg["ticks"] += 1
        offered = 0
        for ns, count in arrivals:
            offered += count
            for _ in range(count):
                try:
                    ticket = admission.admit(ns)
                except AdmissionShed:
                    continue
                queue.append((t, ticket))
        agg["offered"] += offered
        offered_ewma += 0.2 * (offered - offered_ewma)
        # the service stage: capacity decisions leave the queue FIFO
        cap = capacity_tick()
        served = queue[:cap]
        del queue[:cap]
        over = 0
        max_wait = 0.0
        for enq, ticket in served:
            wait = (t - enq) * tick_s
            max_wait = max(max_wait, wait)
            if wait > budget_s:
                over += 1
            ticket.release()
        if served:
            overload.observe(max_wait)
        agg["served"] += len(served)
        agg["over"] += over
        window.append((len(served), over))
        w_served = sum(s for s, _ in window)
        w_over = sum(o for o, o2 in [(o, o) for _, o in window])
        if w_served:
            burn = (w_over / w_served) / slo_target
        agg["max_burn"] = max(agg["max_burn"], round(burn, 2))
        wait_ms = len(queue) / cap * tick_s * 1e3
        # per-tick shed deltas: phase aggregates + the rate signal
        counts = dict(admission._shed_counts)
        rates = {}
        for key, n in counts.items():
            d = n - prev_counts.get(key, 0)
            if d:
                reason, pname = key
                skey = f"{reason}:{pname}"
                agg["sheds"][skey] = agg["sheds"].get(skey, 0) + d
                rates[pname] = rates.get(pname, 0.0) + d / tick_s
        prev_counts = counts
        if controller is not None:
            headroom = 0.0
            if mode == "autoscale" and offered_ewma > 0:
                headroom = cap / offered_ewma
            controller.tick(ControlSignals(
                ts=clock.t, queue_wait_ms=wait_ms,
                slo_burn_5m=round(burn, 4),
                slo_breached=int(burn >= 1.0),
                shed_rate_by_priority=rates,
                capacity_headroom_ratio=headroom,
                model_r2=0.9 if mode == "adaptive" else 0.0,
            ))
        agg["hosts"] = (
            coordinator.router.topology.hosts
            if coordinator is not None else 2
        )

    out = {"mode": mode, "phases": {}}
    for phase in phase_order:
        agg = phases[phase]
        served = agg["served"]
        out["phases"][phase] = {
            "offered_per_s": round(
                agg["offered"] / (agg["ticks"] * tick_s), 1
            ),
            "served_per_s": round(served / (agg["ticks"] * tick_s), 1),
            "over_budget_pct": (
                round(100.0 * agg["over"] / served, 3) if served else 0.0
            ),
            "max_burn": agg["max_burn"],
            "sheds": dict(sorted(agg["sheds"].items())),
            "hosts": agg["hosts"],
        }
    if controller is not None:
        stats = controller.stats()
        out["knob_actuations"] = stats["ctl_knob_actuations"]
        out["hosts_added"] = stats["ctl_hosts_added"]
        out["hosts_drained"] = stats["ctl_hosts_drained"]
        out["final_knobs"] = {
            k: round(v, 2) for k, v in actuator.read().items()
        }
    return out


def bench_controller():
    """ISSUE 20: the self-driving-capacity A/B row plus the autoscale
    segment, all three passes over the SAME open-loop drive (see
    ``controller_drive``). The headline is the adaptive pass's worst
    SLO burn through the load step (must stay < 1.0); the row carries
    the static pass's counterpart, the calm-segment served rates (the
    no-regression guard), the per-class shed split, and the autoscale
    pass's membership actions (one grow + one drain, zero flaps)."""
    static = _controller_sim("static")
    adaptive = _controller_sim("adaptive")
    autoscale = _controller_sim("autoscale")
    for row in (static, adaptive, autoscale):
        for phase, p in row["phases"].items():
            print(
                f"{row['mode']:>9} {phase:>5}: offered {p['offered_per_s']:7.1f}/s "
                f"served {p['served_per_s']:7.1f}/s "
                f"over-budget {p['over_budget_pct']:6.2f}% "
                f"max-burn {p['max_burn']:8.2f} hosts {p['hosts']}",
                file=sys.stderr,
            )
    sheds_of = lambda row, phase: row["phases"][phase]["sheds"]  # noqa: E731
    print(
        "step sheds static "
        f"{sheds_of(static, 'step')} vs adaptive "
        f"{sheds_of(adaptive, 'step')}",
        file=sys.stderr,
    )
    print(
        f"autoscale: +{autoscale['hosts_added']} host on the step, "
        f"-{autoscale['hosts_drained']} at night, final "
        f"{autoscale['phases']['night']['hosts']} hosts",
        file=sys.stderr,
    )
    # floor at 0.01 so the improvement ratio stays finite: a clean run
    # holds burn at literally zero through the step
    emit(
        "controller_step_slo_burn",
        max(adaptive["phases"]["step"]["max_burn"], 0.01),
        "burn", 1.0, ndigits=3, lower_is_better=True,
        mode="adaptive",
        static_step_burn=static["phases"]["step"]["max_burn"],
        static_ramp_burn=static["phases"]["ramp"]["max_burn"],
        adaptive_ramp_burn=adaptive["phases"]["ramp"]["max_burn"],
        calm_served_static=static["phases"]["calm"]["served_per_s"],
        calm_served_adaptive=adaptive["phases"]["calm"]["served_per_s"],
        step_sheds_static=sheds_of(static, "step"),
        step_sheds_adaptive=sheds_of(adaptive, "step"),
        knob_actuations=adaptive["knob_actuations"],
        final_knobs=adaptive["final_knobs"],
        autoscale={
            "hosts_added": autoscale["hosts_added"],
            "hosts_drained": autoscale["hosts_drained"],
            "step_hosts": autoscale["phases"]["step"]["hosts"],
            "night_hosts": autoscale["phases"]["night"]["hosts"],
            "step_burn": autoscale["phases"]["step"]["max_burn"],
        },
    )


def bench_tiered():
    """ISSUE 17: tiered storage under the large-keyspace regime. Sweeps
    the logical keyspace across three decades (1M / 10M / 100M keys)
    against a FIXED device table: a Zipf-distributed batched decision
    stream — only touched keys materialize, so the stream length is the
    honest coverage bound and rides every row as ``decision_bound`` —
    with TierManager rounds interleaved so heat promotes the working
    set device-side while the LRU tail demotes exactly into the cold
    tier. Per-keyspace rows report the device/cold resident split, the
    cold share of decisions and the per-tier per-decision p50/p99; the
    final row is the headline claim — the device-resident p99 stays
    flat while the keyspace grows 100x past device capacity."""
    import os

    from limitador_tpu import Limit
    from limitador_tpu.core.counter import Counter
    from limitador_tpu.tier import TieredStorage, TierManager
    from limitador_tpu.tpu.storage import _Request

    require_chip()

    decisions = int(os.environ.get("BENCH_TIER_DECISIONS", "40000"))
    batch = 256
    # Device table sized WELL below the stream's unique-key count so the
    # tail must spill cold whatever the decision bound is set to.
    cache_size = max(256, min(1 << 13, decisions // 8))
    capacity = cache_size * 2
    limit = Limit("ns", 10**9, 60, [], ["u"])
    rng = np.random.default_rng(17)
    device_p99_by_keyspace = {}
    for keyspace in (1_000_000, 10_000_000, 100_000_000):
        storage = TieredStorage(capacity=capacity, cache_size=cache_size)
        mgr = TierManager(storage, interval_s=3600.0, batch=1024)
        # Zipf ranks folded into the keyspace: a heavy head that fits
        # the device table plus a long tail that must spill cold.
        keys = (rng.zipf(1.1, size=decisions) - 1) % keyspace
        # Untimed warmup, structurally identical to the timed loop
        # (same batch shape, same interleaved manager rounds): compiles
        # the check/evict/peek/seed kernels and fills the table so the
        # timed phase measures steady-state churn.
        warm = (rng.zipf(1.1, size=16 * batch) - 1) % keyspace
        for off in range(0, warm.size, batch):
            storage.check_many([
                _Request([Counter(limit, {"u": str(int(k))})], 1, False)
                for k in warm[off:off + batch]
            ])
            if (off // batch) % 8 == 7:
                mgr.run_once()
        # Cold hits shrink a batch's device half, so the mixed stream
        # produces every pow2 launch bucket up to the batch size —
        # compile them all now (Zipf head keys are device-resident).
        size = 1
        while size <= batch:
            storage.check_many([
                _Request([Counter(limit, {"u": str(i)})], 1, False)
                for i in range(size)
            ])
            size *= 2
        storage.drain_cold_decide_samples()
        device_per_dec = []
        cold_per_dec = []
        cold_total = 0
        t0 = time.perf_counter()
        for off in range(0, decisions, batch):
            chunk = keys[off:off + batch]
            reqs = [
                _Request([Counter(limit, {"u": str(int(k))})], 1, False)
                for k in chunk
            ]
            c0 = storage._cold.decisions
            storage.drain_cold_decide_samples()
            b0 = time.perf_counter()
            storage.check_many(reqs)
            bdt = time.perf_counter() - b0
            cold_n = storage._cold.decisions - c0
            cold_total += cold_n
            cold_dt = sum(storage.drain_cold_decide_samples())
            if cold_n:
                cold_per_dec.append(cold_dt / cold_n)
            dev_n = len(chunk) - cold_n
            if dev_n:
                device_per_dec.append(max(bdt - cold_dt, 0.0) / dev_n)
            if (off // batch) % 8 == 7:
                mgr.run_once()
        wall = time.perf_counter() - t0
        mgr.run_once()
        stats = storage.tier_stats()
        touched = int(np.unique(keys).size)
        dev_us = np.asarray(device_per_dec) * 1e6
        cold_us = np.asarray(cold_per_dec) * 1e6
        dev_p50 = float(np.percentile(dev_us, 50)) if dev_us.size else 0.0
        dev_p99 = float(np.percentile(dev_us, 99)) if dev_us.size else 0.0
        cold_p50 = float(np.percentile(cold_us, 50)) if cold_us.size else 0.0
        cold_p99 = float(np.percentile(cold_us, 99)) if cold_us.size else 0.0
        device_p99_by_keyspace[keyspace] = dev_p99
        print(
            f"tiered @ {keyspace/1e6:.0f}M keys: "
            f"{decisions/wall/1e3:.1f}k decisions/s, "
            f"{touched} touched ({stats['device_resident']} device / "
            f"{stats['cold']['resident']} cold resident), "
            f"cold share {cold_total/decisions:.1%}, "
            f"device p99 {dev_p99:.1f}us, cold p99 {cold_p99:.1f}us, "
            f"{mgr.promoted} promoted / {mgr.demoted} demoted",
            file=sys.stderr,
        )
        emit(
            "tiered_decisions_per_sec", decisions / wall, "decisions/s",
            1e5, keyspace=keyspace, decision_bound=decisions,
            touched_keys=touched,
            device_resident=stats["device_resident"],
            cold_resident=stats["cold"]["resident"],
            resident_share=round(
                stats["device_resident"] / max(touched, 1), 4
            ),
            cold_share=round(cold_total / decisions, 4),
            device_decide_p50_us=round(dev_p50, 2),
            device_decide_p99_us=round(dev_p99, 2),
            cold_decide_p50_us=round(cold_p50, 2),
            cold_decide_p99_us=round(cold_p99, 2),
            migrations_promoted=mgr.promoted,
            migrations_demoted=mgr.demoted,
        )
        mgr.close()
        storage.close()
    # The headline: device-resident per-decision p99 across the sweep,
    # worst/best ratio (1.0 = perfectly flat across 100x keyspace).
    p99s = [v for v in device_p99_by_keyspace.values() if v > 0]
    flatness = (max(p99s) / min(p99s)) if p99s else 0.0
    print(
        f"tiered device p99 flatness across 1M->100M keys: "
        f"{flatness:.2f}x (1.0 = flat)",
        file=sys.stderr,
    )
    emit(
        "tiered_device_p99_flatness", flatness, "ratio", 2.0,
        ndigits=3, lower_is_better=True,
        device_p99_us_by_keyspace={
            str(k): round(v, 2) for k, v in device_p99_by_keyspace.items()
        },
    )


class _LatencySink:
    """Duck-typed metrics object for the batcher: collects the
    queue-excluded per-request device round-trip (the datastore
    latency the reference's MetricsLayer measures)."""

    def __init__(self):
        self.samples = []
        sink = self

        class _H:
            @staticmethod
            def observe(dt):
                sink.samples.append(dt)

        self.datastore_latency = _H()

    def custom_labels(self, ctx):
        return {}

    def percentiles(self):
        lat_ms = np.asarray(self.samples) * 1e3
        return (
            round(float(np.percentile(lat_ms, 50)), 3),
            round(float(np.percentile(lat_ms, 99)), 3),
        )


def bench_pipeline():
    """Config 2: full compiled pipeline — descriptor replay, 100k keys.

    Runs TWO dispatch disciplines over the same driver (ISSUE 4): a
    monolithic pass (``dispatch_chunk=0`` — every flush is one kernel
    launch, the pre-chunking behavior) for the
    ``datastore_*_ms_monolithic`` baseline, then the chunked-dispatch
    sweep (auto-planned sub-batches) whose throughput and datastore
    latency are the recorded headline. ``dispatch_chunk_p99_speedup`` =
    monolithic p99 / chunked p99 at the same drive."""
    import asyncio
    import threading

    from limitador_tpu import Limit
    from limitador_tpu.core.limit import Namespace
    from limitador_tpu.tpu import AsyncTpuStorage, TpuStorage
    from limitador_tpu.tpu.pipeline import CompiledTpuLimiter

    require_chip()
    rng = np.random.default_rng(0)
    users = [str(int(x)) for x in rng.integers(0, 100_000, 200_000)]
    ns = Namespace.of("api")

    def build(dispatch_chunk):
        from limitador_tpu.core.counter import Counter
        from limitador_tpu.tpu.storage import _Request

        sink = _LatencySink()
        inner = TpuStorage(capacity=1 << 17)
        storage = AsyncTpuStorage(
            inner,
            max_delay=0.002,
            max_batch_hits=16384,
            dispatch_chunk=dispatch_chunk,
        )
        limiter = CompiledTpuLimiter(storage, dispatch_chunk=dispatch_chunk)
        # The compiled fast path observes through the limiter's own
        # metrics hook (exotic-context fallbacks route to the
        # micro-batcher, which set_metrics wires up too).
        limiter.set_metrics(sink)
        limiter.max_batch = 16384
        limit = Limit("api", 10**6, 60,
                      ["descriptors[0].m == 'GET'"], ["descriptors[0].u"])
        limiter.add_limit(limit)
        # Pre-compile every kernel hit-bucket the chunk planner can
        # produce: a first-touch XLA compile mid-measurement records as
        # a ~300ms latency spike that is compiler state, not dispatch
        # behavior.
        for size in (512, 1024, 2048, 4096, 8192, 16384):
            inner.check_many([
                _Request([Counter(limit, {"u": f"warm-{i}"})], 1, False)
                for i in range(size)
            ])
        return limiter, sink

    def drive_shards(limiter, shards: int, n: int = 100_000) -> float:
        """Thread-per-loop serving shards over
        ``check_rate_limited_and_update`` — the SAME per-request surface
        the gRPC handlers await and the same one every earlier round's
        pipeline row measured (driving the bare submit_check fast lane
        would inflate the row by skipping the handler-path work), split
        evenly across shards."""
        per = n // shards

        async def worker(base):
            check = limiter.check_rate_limited_and_update
            for ofs in range(0, per, 8192):
                wave = min(8192, per - ofs)
                await asyncio.gather(*[
                    check(ns, {
                        "m": "GET",
                        "u": users[(base + ofs + i) % len(users)],
                    }, 1)
                    for i in range(wave)
                ])

        def run_one(base):
            loop = asyncio.new_event_loop()
            loop.run_until_complete(worker(base))
            loop.close()

        threads = [
            threading.Thread(target=run_one, args=(k * per,))
            for k in range(shards)
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return shards * per / (time.perf_counter() - t0)

    def teardown(limiter):
        async def _close():
            await limiter.close()
            await limiter.storage.counters.close()

        asyncio.new_event_loop().run_until_complete(_close())

    # -- monolithic baseline (one launch per flush) -----------------------
    limiter, sink = build(0)
    drive_shards(limiter, 1, n=16384)  # warm: kernel buckets + counters
    sink.samples.clear()
    mono_rate = drive_shards(limiter, 1, n=60_000)
    mono_p50, mono_p99 = sink.percentiles()
    mono_samples = len(sink.samples)
    teardown(limiter)
    print(
        f"monolithic dispatch: {mono_rate/1e3:.1f}k decisions/s, "
        f"datastore p50 {mono_p50}ms p99 {mono_p99}ms "
        f"over {mono_samples} requests",
        file=sys.stderr,
    )

    # -- chunked dispatch (the recorded discipline) -----------------------
    limiter, sink = build(None)  # auto-planned sub-batches
    # Warm enough flushes for the planner's device-time EWMA to settle
    # and every chunk bucket to compile before anything is measured.
    drive_shards(limiter, 1, n=32768)
    sink.samples.clear()
    rate = drive_shards(limiter, 1, n=60_000)
    chunk_p50, chunk_p99 = sink.percentiles()
    chunk_samples = len(sink.samples)
    best_shards = 1
    for shards in (2, 4):
        shard_rate = drive_shards(limiter, shards)
        if shard_rate > rate:
            rate, best_shards = shard_rate, shards
    # The recorded datastore_* fields are the 1-shard chunked pass —
    # like-for-like against the monolithic baseline (the multi-shard
    # sweep stacks several inflight windows onto one device queue, which
    # measures contention, not dispatch discipline).
    extra = {
        "datastore_p50_ms": chunk_p50,
        "datastore_p99_ms": chunk_p99,
        "datastore_samples": chunk_samples,
        "datastore_p50_ms_monolithic": mono_p50,
        "datastore_p99_ms_monolithic": mono_p99,
        "pipeline_mono_decisions_per_sec": round(mono_rate, 1),
        "dispatch_chunk_p99_speedup": (
            round(mono_p99 / chunk_p99, 2) if chunk_p99 > 0 else 0.0
        ),
    }
    print(
        f"datastore latency (queue-excluded device round trip): "
        f"chunked p50 {chunk_p50}ms p99 {chunk_p99}ms vs monolithic "
        f"p50 {mono_p50}ms p99 {mono_p99}ms at 1 shard "
        f"({extra['dispatch_chunk_p99_speedup']}x p99 over "
        f"{chunk_samples} requests)",
        file=sys.stderr,
    )
    print(f"compiled pipeline: {rate/1e3:.1f}k decisions/s "
          f"(python host path end-to-end, best at {best_shards} serving "
          "shard(s))", file=sys.stderr)
    extra["pipeline_shards"] = best_shards
    cache = limiter.counters_cache
    if cache is not None:
        extra["pipeline_plan_cache_hit_ratio"] = round(cache.hit_ratio, 4)
    teardown(limiter)
    emit("pipeline_decisions_per_sec", rate, "decisions/s", 1e7, **extra)


def bench_native():
    """Native columnar serving path: raw RLS blobs -> C++ hot lane (or
    parse -> masks -> slots on misses) -> device kernel -> response
    blobs.

    Every headline runs TWICE in this process — zero-Python hot lane ON
    (the default) and OFF (the pure-Python cached/parse lanes) — and the
    recorded speedups are those same-process, same-box ratios; absolute
    rates carry ``box_calibration_score`` for cross-round context but
    are NOT comparable across rounds on their own (ISSUE 5 satellite).
    The served row sweeps SERVING SHARDS (thread-per-event-loop); the
    ingress row drives the vendored C++ HTTP/2 ingress in-process over
    real sockets with the pump's batch-coded answer path on vs off."""
    import asyncio
    import os
    import threading

    from limitador_tpu import Limit, native
    from limitador_tpu.server.proto import rls_pb2
    from limitador_tpu.tpu import AsyncTpuStorage, TpuStorage
    from limitador_tpu.tpu.native_pipeline import NativeRlsPipeline
    from limitador_tpu.tpu.pipeline import CompiledTpuLimiter

    require_chip()
    if not native.available():
        print("native unavailable:", native.build_error(), file=sys.stderr)
        emit("native_pipeline_decisions_per_sec", 0.0, "decisions/s", 1e7)
        return

    # Arm the native telemetry plane so this row carries the drained
    # per-phase percentiles (ISSUE 7 acceptance: native_phase_* in
    # bench JSON rows; the serving/grpc rows scrape the same families
    # off /metrics instead).
    from limitador_tpu.observability.native_plane import NativePlane

    tel_plane = NativePlane()

    rng = np.random.default_rng(0)
    blobs = []
    for i in range(1 << 15):
        req = rls_pb2.RateLimitRequest(domain="api")
        d = req.descriptors.add()
        e = d.entries.add(); e.key = "m"; e.value = "GET"
        e = d.entries.add(); e.key = "u"
        e.value = f"user-{int(rng.integers(0, 100_000))}"
        blobs.append(req.SerializeToString())

    def build(hot):
        limiter = CompiledTpuLimiter(
            AsyncTpuStorage(TpuStorage(capacity=1 << 17), max_delay=0.001)
        )
        limiter.add_limit(
            Limit("api", 10**6, 60,
                  ["descriptors[0].m == 'GET'"], ["descriptors[0].u"])
        )
        return NativeRlsPipeline(
            limiter, None, max_delay=0.001, hot_lane=hot
        ), limiter

    def engine_rate_of(pipeline) -> float:
        # One timed engine pass: raw blobs -> response blobs through
        # decide_many, zero per-request asyncio. Full-list chunks
        # amortize the link round trip. Callers warm first and
        # interleave on/off passes (this box swings 2-6x mid-run; a
        # sequential A-then-B comparison measures the drift, not the
        # code).
        chunk = len(blobs)
        n = 0
        t0 = time.perf_counter()
        for _ in range(4):
            n += len(pipeline.decide_many(blobs, chunk=chunk))
        return n / (time.perf_counter() - t0)

    def drive_shards(pipeline, shards: int, reps: int = 3) -> float:
        # Serving path: per-request futures through the sharded asyncio
        # submit lane (the grpc.aio integration surface).
        parts = [blobs[i::shards] for i in range(shards)]

        async def worker(part):
            futs = []
            submit = pipeline.submit
            for _ in range(reps):
                for b in part:
                    futs.append(submit(b))
                    if len(futs) >= 8192:
                        await asyncio.gather(*futs)
                        futs = []
            if futs:
                await asyncio.gather(*futs)

        def run_one(part):
            loop = asyncio.new_event_loop()
            loop.run_until_complete(worker(part))
            loop.close()

        threads = [
            threading.Thread(target=run_one, args=(p,)) for p in parts
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return reps * len(blobs) / (time.perf_counter() - t0)

    def teardown(pipeline, limiter):
        async def go():
            await pipeline.close()
            await limiter.storage.counters.close()

        loop = asyncio.new_event_loop()
        loop.run_until_complete(go())
        loop.close()

    # Both pipelines live side by side and every comparison interleaves
    # on/off passes, best-of per mode: the box swings 2-6x mid-run, so a
    # sequential off-pass-then-on-pass would record scheduler drift, not
    # the lane. The ratios below are same-process, same-box by
    # construction.
    p_off, lim_off = build(False)
    pipeline, limiter = build(None)
    hot_active = pipeline.hot_lane_active
    chunk = len(blobs)
    p_off.decide_many(blobs, chunk=chunk)  # warm: buckets/slots/plans
    pipeline.decide_many(blobs, chunk=chunk)
    engine_off = engine_rate = 0.0
    for _rep in range(3):
        engine_off = max(engine_off, engine_rate_of(p_off))
        engine_rate = max(engine_rate, engine_rate_of(pipeline))

    drive_shards(p_off, 1, reps=1)  # warm shard + plan cache
    drive_shards(pipeline, 1, reps=1)
    serving_off = serving_on_1 = 0.0
    for _rep in range(2):
        serving_off = max(serving_off, drive_shards(p_off, 1))
        serving_on_1 = max(serving_on_1, drive_shards(pipeline, 1))
    serving_rate = serving_on_1
    serving_shards = 1
    by_shards = {"1": round(serving_on_1, 1)}
    shard_counts = [2, 4]
    cores = os.cpu_count() or 1
    if cores >= 8:
        shard_counts.append(8)
    for shards in shard_counts:
        rate = drive_shards(pipeline, shards)
        by_shards[str(shards)] = round(rate, 1)
        if rate > serving_rate:
            serving_rate, serving_shards = rate, shards

    ingress_off = ingress_on = 0.0
    for _rep in range(2):
        ingress_off = max(
            ingress_off, _drive_native_ingress(p_off, blobs)
        )
        ingress_on = max(
            ingress_on, _drive_native_ingress(pipeline, blobs)
        )
    cache = pipeline.plan_cache
    hit_ratio = round(cache.hit_ratio, 4) if cache is not None else 0.0
    lane_stats = pipeline.lane_stats()

    teardown(p_off, lim_off)
    teardown(pipeline, limiter)
    engine_speedup = round(engine_rate / engine_off, 2) if engine_off else 0.0
    serving_speedup = (
        round(serving_on_1 / serving_off, 2) if serving_off else 0.0
    )
    ingress_speedup = (
        round(ingress_on / ingress_off, 2)
        if ingress_on and ingress_off else 0.0
    )
    print(
        f"native pipeline (hot lane {'on' if hot_active else 'OFF'}): "
        f"{engine_rate/1e3:.1f}k decisions/s engine "
        f"({engine_speedup}x vs lane-off {engine_off/1e3:.1f}k), "
        f"{serving_rate/1e3:.1f}k served best at {serving_shards} "
        f"shard(s) (sweep {by_shards}; 1-shard {serving_speedup}x vs "
        f"lane-off {serving_off/1e3:.1f}k), ingress "
        f"{ingress_on/1e3:.1f}k req/s ({ingress_speedup}x vs lane-off "
        f"{ingress_off/1e3:.1f}k), plan-cache hit ratio {hit_ratio}, "
        f"lane rows {lane_stats.get('hits', 0)}",
        file=sys.stderr,
    )
    emit(
        "native_pipeline_decisions_per_sec", engine_rate, "decisions/s", 1e7,
        native_serving_decisions_per_sec=round(serving_rate, 1),
        native_serving_shards=serving_shards,
        native_serving_by_shards=by_shards,
        plan_cache_hit_ratio=hit_ratio,
        hot_lane_active=hot_active,
        native_engine_off_decisions_per_sec=round(engine_off, 1),
        native_hot_lane_engine_speedup=engine_speedup,
        native_serving_off_decisions_per_sec=round(serving_off, 1),
        native_hot_lane_serving_speedup=serving_speedup,
        native_ingress_rps=round(ingress_on, 1),
        native_ingress_off_rps=round(ingress_off, 1),
        native_hot_lane_ingress_speedup=ingress_speedup,
        native_lane_staged_hits=lane_stats.get("staged_hits", 0),
        native_phase_us={
            phase: stats
            for phase, stats in tel_plane.native_telemetry().items()
            if stats.get("count")
        },
    )


def bench_lease():
    """Quota-lease tier (ISSUE 6): lease on vs off, interleaved in THIS
    process on the SAME box — the recorded ``lease_engine_speedup`` /
    ``lease_serving_speedup`` are same-process ratios (absolutes carry
    ``box_calibration_score`` + ``device_backed`` like every row).

    The drive is Zipf-shaped (hot keys dominate — the workload leasing
    exists for): the lease-on pipeline runs a live broker topping up
    hot plans, so repeat decisions complete with zero device work;
    the off pipeline rides the plain hot lane (plan mirror + kernel
    launch per batch). Hot-descriptor engine latency is sampled
    per-batch into p50/p99 ns/row for the leased lane."""
    import asyncio
    import threading

    from limitador_tpu import Limit, native
    from limitador_tpu.server.proto import rls_pb2
    from limitador_tpu.tpu import AsyncTpuStorage, TpuStorage
    from limitador_tpu.tpu.native_pipeline import NativeRlsPipeline
    from limitador_tpu.tpu.pipeline import CompiledTpuLimiter

    require_chip()
    if not native.available() or not native.lease_available():
        print("native lease lane unavailable:", native.build_error(),
              file=sys.stderr)
        emit("lease_decisions_per_sec", 0.0, "decisions/s", 1e7)
        return

    # Hot-descriptor drive: Zipf over a SMALL key space so every key is
    # genuinely hot (the workload leasing exists for — broad key spaces
    # are the plain hot-lane bench's territory). With full lease
    # coverage, whole batches decide with ZERO kernel launches.
    rng = np.random.default_rng(0)
    users = zipf_keys(128, 1 << 15, 1.2, rng)
    blobs = []
    for u in users.tolist():
        req = rls_pb2.RateLimitRequest(domain="api")
        d = req.descriptors.add()
        e = d.entries.add(); e.key = "m"; e.value = "GET"
        e = d.entries.add(); e.key = "u"; e.value = f"user-{u}"
        blobs.append(req.SerializeToString())

    def build(lease: bool):
        limiter = CompiledTpuLimiter(
            AsyncTpuStorage(TpuStorage(capacity=1 << 17), max_delay=0.001)
        )
        limiter.add_limit(
            Limit("api", 10**8, 60,
                  ["descriptors[0].m == 'GET'"], ["descriptors[0].u"])
        )
        pipeline = NativeRlsPipeline(
            limiter, None, max_delay=0.001, hot_lane=True
        )
        broker = None
        if lease:
            from limitador_tpu.lease import LeaseConfig

            broker = pipeline.attach_lease(LeaseConfig(
                max_tokens=1 << 17, hot_threshold=1, ttl_s=30.0,
                refresh_interval_s=0.01,
            ))
        return pipeline, limiter, broker

    def engine_rate_of(pipeline, samples=None) -> float:
        chunk = 4096
        n = 0
        t0 = time.perf_counter()
        for _rep in range(2):
            for ofs in range(0, len(blobs), chunk):
                part = blobs[ofs:ofs + chunk]
                tb = time.perf_counter()
                pipeline.decide_many(part, chunk=chunk)
                if samples is not None:
                    samples.append(
                        (time.perf_counter() - tb) / len(part) * 1e9
                    )
                n += len(part)
        return n / (time.perf_counter() - t0)

    def drive_serving(pipeline, reps: int = 2) -> float:
        async def worker():
            futs = []
            submit = pipeline.submit
            for _ in range(reps):
                for b in blobs:
                    futs.append(submit(b))
                    if len(futs) >= 8192:
                        await asyncio.gather(*futs)
                        futs = []
            if futs:
                await asyncio.gather(*futs)

        def run_one():
            loop = asyncio.new_event_loop()
            loop.run_until_complete(worker())
            loop.close()

        t = threading.Thread(target=run_one)
        t0 = time.perf_counter()
        t.start()
        t.join()
        return reps * len(blobs) / (time.perf_counter() - t0)

    def teardown(pipeline, limiter):
        async def go():
            await pipeline.close()
            await limiter.storage.counters.close()

        loop = asyncio.new_event_loop()
        loop.run_until_complete(go())
        loop.close()

    p_off, lim_off, _ = build(False)
    p_on, lim_on, broker = build(True)
    # warm both: derive plans, compile kernel buckets, then let the
    # broker's demand-doubling size leases up to full pass coverage
    p_off.decide_many(blobs, chunk=4096)
    for _ in range(6):
        p_on.decide_many(blobs, chunk=4096)
        broker.refresh()

    engine_off = engine_on = 0.0
    hot_ns = []
    for _rep in range(3):  # interleaved best-of (the box swings mid-run)
        engine_off = max(engine_off, engine_rate_of(p_off))
        engine_on = max(engine_on, engine_rate_of(p_on, samples=hot_ns))
        broker.refresh()

    # Serving = the C++ HTTP/2 ingress with batch-coded answers (the
    # plane leased traffic actually serves from: zero per-request
    # Python, so removing the kernel launch is visible). The asyncio
    # submit lane rides along as lease_submit_*: its ~20µs/request of
    # future machinery dominates regardless of the device phase.
    serving_off = serving_on = 0.0
    try:
        _drive_native_ingress(p_off, blobs, waves=10)  # warm
        _drive_native_ingress(p_on, blobs, waves=10)
        for _rep in range(2):
            serving_off = max(
                serving_off, _drive_native_ingress(p_off, blobs)
            )
            broker.refresh()
            serving_on = max(
                serving_on, _drive_native_ingress(p_on, blobs)
            )
    except Exception as exc:
        print(f"lease ingress drive unavailable ({exc}); serving "
              "ratio falls back to the submit lane", file=sys.stderr)
    drive_serving(p_off, reps=1)  # warm the submit shard
    drive_serving(p_on, reps=1)
    submit_off = submit_on = 0.0
    for _rep in range(2):
        submit_off = max(submit_off, drive_serving(p_off))
        broker.refresh()
        submit_on = max(submit_on, drive_serving(p_on))
    if not (serving_on and serving_off):
        serving_on, serving_off = submit_on, submit_off

    stats = broker.stats()
    lane = p_on.lane_stats()
    total_rows = lane.get("hits", 0) + lane.get("misses", 0)
    leased_share = (
        stats["lease_admissions"] / total_rows if total_rows else 0.0
    )
    teardown(p_off, lim_off)
    teardown(p_on, lim_on)

    hot = np.asarray(hot_ns) if hot_ns else np.zeros(1)
    p50_ns, p99_ns = float(np.percentile(hot, 50)), float(
        np.percentile(hot, 99)
    )
    engine_speedup = round(engine_on / engine_off, 2) if engine_off else 0.0
    serving_speedup = (
        round(serving_on / serving_off, 2) if serving_off else 0.0
    )
    submit_speedup = (
        round(submit_on / submit_off, 2) if submit_off else 0.0
    )
    print(
        f"lease tier: engine {engine_on/1e3:.1f}k dec/s "
        f"({engine_speedup}x vs lease-off {engine_off/1e3:.1f}k), served "
        f"(ingress) {serving_on/1e3:.1f}k ({serving_speedup}x vs "
        f"lease-off {serving_off/1e3:.1f}k), submit lane "
        f"{submit_on/1e3:.1f}k ({submit_speedup}x), hot p50 "
        f"{p50_ns:.0f}ns p99 {p99_ns:.0f}ns/row, leased share "
        f"{leased_share:.3f}, grants {stats['lease_grants']} "
        f"(denied {stats['lease_grant_denials']}), returned "
        f"{stats['lease_returned_tokens']} tokens",
        file=sys.stderr,
    )
    emit(
        "lease_decisions_per_sec", engine_on, "decisions/s", 1e7,
        lease_engine_off_decisions_per_sec=round(engine_off, 1),
        lease_engine_speedup=engine_speedup,
        lease_serving_decisions_per_sec=round(serving_on, 1),
        lease_serving_off_decisions_per_sec=round(serving_off, 1),
        lease_serving_speedup=serving_speedup,
        lease_submit_decisions_per_sec=round(submit_on, 1),
        lease_submit_off_decisions_per_sec=round(submit_off, 1),
        lease_submit_speedup=submit_speedup,
        lease_hot_p50_ns=round(p50_ns, 1),
        lease_hot_p99_ns=round(p99_ns, 1),
        lease_admissions=stats["lease_admissions"],
        lease_leased_share=round(leased_share, 4),
        lease_grants=stats["lease_grants"],
        lease_grant_denials=stats["lease_grant_denials"],
        lease_returned_tokens=stats["lease_returned_tokens"],
    )


def _h2_frame(ftype: int, flags: int, stream: int, payload: bytes) -> bytes:
    return (
        len(payload).to_bytes(3, "big") + bytes([ftype, flags])
        + stream.to_bytes(4, "big") + payload
    )


def _drive_native_ingress(pipeline, blobs, waves: int = 40,
                          wave_size: int = 512) -> float:
    """Served throughput through the vendored C++ HTTP/2 ingress over a
    real socket, in-process, with a RAW pipelined h2 client: each wave
    pre-serializes HEADERS+DATA for ``wave_size`` streams (static-table
    HPACK only) and is written with one sendall, then responses are
    drained counting END_STREAM trailers. A python-gRPC closed loop
    measures its own per-call overhead (~1ms/req on this box) instead
    of the server; this driver keeps the pump fed with real batches, so
    the recorded hot-lane on/off ratio isolates the server-side answer
    path (batch-coded respond vs per-row). Returns req/s (0.0 when the
    ingress library is unavailable)."""
    import asyncio
    import socket
    import threading as _threading

    try:
        from limitador_tpu.native.ingress import (
            NativeIngress,
            ingress_available,
        )
    except Exception as exc:
        print(f"ingress drive skipped: {exc}", file=sys.stderr)
        return 0.0
    if not ingress_available():
        return 0.0

    loop = asyncio.new_event_loop()
    lt = _threading.Thread(target=loop.run_forever, daemon=True)
    lt.start()
    ing = NativeIngress(pipeline, host="127.0.0.1", port=0, loop=loop,
                        poll_ms=1, max_batch=wave_size)
    path = b"/envoy.service.ratelimit.v3.RateLimitService/ShouldRateLimit"
    # :method POST (static idx 3), :scheme http (6), :path literal
    # (name idx 4), content-type literal (name idx 31) — no dynamic
    # table, so every stream reuses one prebuilt block.
    ct = b"application/grpc"
    headers = (
        bytes([0x83, 0x86, 0x04, len(path)]) + path
        + bytes([0x0F, 0x10, len(ct)]) + ct
    )
    subset = blobs[:512]  # repeated -> the plan caches serve steady state

    def build_waves(n_waves, first_stream):
        bufs, sid = [], first_stream
        for _w in range(n_waves):
            parts = []
            for i in range(wave_size):
                blob = subset[(sid // 2) % len(subset)]
                grpc_msg = b"\x00" + len(blob).to_bytes(4, "big") + blob
                parts.append(_h2_frame(1, 0x4, sid, headers))
                parts.append(_h2_frame(0, 0x1, sid, grpc_msg))
                sid += 2
            bufs.append(b"".join(parts))
        return bufs, sid

    def drain(sock, buf: bytearray, expect: int) -> None:
        # Count trailer frames (HEADERS with END_STREAM): one per
        # answered stream. The server's connection send window is
        # refilled promptly for received DATA bytes (else it parks
        # responses after ~64KB).
        done = 0
        data_bytes = 0
        while done < expect:
            data = sock.recv(1 << 18)
            if not data:
                raise ConnectionError("ingress closed mid-drive")
            buf += data
            off = 0
            while len(buf) - off >= 9:
                flen = int.from_bytes(buf[off:off + 3], "big")
                if len(buf) - off < 9 + flen:
                    break
                ftype = buf[off + 3]
                if ftype == 1 and buf[off + 4] & 0x1:
                    done += 1
                elif ftype == 0:
                    data_bytes += flen
                off += 9 + flen
            del buf[:off]
            if data_bytes >= 8192:
                sock.sendall(
                    _h2_frame(8, 0, 0, data_bytes.to_bytes(4, "big"))
                )
                data_bytes = 0
        if data_bytes:
            sock.sendall(
                _h2_frame(8, 0, 0, data_bytes.to_bytes(4, "big"))
            )

    rate = 0.0
    try:
        sock = socket.create_connection(("127.0.0.1", ing.port),
                                        timeout=30)
        sock.settimeout(60)
        sock.sendall(
            b"PRI * HTTP/2.0\r\n\r\nSM\r\n\r\n" + _h2_frame(4, 0, 0, b"")
        )
        rbuf = bytearray()
        warm_bufs, next_sid = build_waves(4, 1)
        for buf in warm_bufs:  # warm: slots, plan caches, kernel buckets
            sock.sendall(buf)
            drain(sock, rbuf, wave_size)
        # Two timed passes, best-of: wave-sized bursts (one sendall,
        # full drain) keep the measurement stable on a contended box —
        # full streaming thrashes the 2-core CI container's scheduler
        # and swings 10x run to run.
        for _pass in range(2):
            wave_bufs, next_sid = build_waves(waves, next_sid)
            t0 = time.perf_counter()
            for buf in wave_bufs:
                sock.sendall(buf)
                drain(sock, rbuf, wave_size)
            rate = max(
                rate, waves * wave_size / (time.perf_counter() - t0)
            )
        sock.close()
    except Exception as exc:
        print(f"ingress drive failed: {exc}", file=sys.stderr)
    finally:
        ing.close()
        loop.call_soon_threadsafe(loop.stop)
        lt.join(timeout=5)
        loop.close()
    return rate


def bench_backends():
    """Reference criterion-scenario parity (limitador/benches/bench.rs):
    is_rate_limited / check_rate_limited_and_update / update_counters per
    backend. Prints a table to stderr; emits the tpu check rate."""
    import tempfile

    from limitador_tpu import Context, Limit, RateLimiter
    from limitador_tpu.storage.disk import DiskStorage
    from limitador_tpu.storage.distributed import CrInMemoryStorage
    from limitador_tpu.storage.in_memory import InMemoryStorage
    from limitador_tpu.tpu.storage import TpuStorage

    require_chip()

    def backends():
        yield "memory", InMemoryStorage()
        yield "tpu", TpuStorage(capacity=1 << 16)
        yield "disk", DiskStorage(
            tempfile.mkdtemp(prefix="bench-disk-") + "/c.db"
        )
        yield "distributed", CrInMemoryStorage.standalone("bench")

    # scenario: 10 limits/namespace x (1 condition, 1 variable)
    limits = [
        Limit("ns", 10**9, 60, [f"descriptors[0].m == 'm{i}'"],
              ["descriptors[0].u"])
        for i in range(10)
    ]
    ctxs = []
    for i in range(200):
        ctx = Context()
        ctx.list_binding(
            "descriptors", [{"m": f"m{i % 10}", "u": f"user{i % 50}"}]
        )
        ctxs.append(ctx)

    print(
        "note: per-call (unbatched) tpu ops pay one device sync each; "
        "production throughput comes from the batched paths (configs "
        "device/native), not this per-call matrix",
        file=sys.stderr,
    )
    tpu_rate = 0.0
    for name, storage in backends():
        limiter = RateLimiter(storage)
        for l in limits:
            limiter.add_limit(l)
        rates = {}
        for op, fn in (
            ("is_rate_limited",
             lambda c: limiter.is_rate_limited("ns", c, 1)),
            ("check_and_update",
             lambda c: limiter.check_rate_limited_and_update("ns", c, 1)),
            ("update_counters",
             lambda c: limiter.update_counters("ns", c, 1)),
        ):
            n = 500 if name != "tpu" else 200
            fn(ctxs[0])  # warm
            t0 = time.perf_counter()
            for i in range(n):
                fn(ctxs[i % 200])
            rates[op] = n / (time.perf_counter() - t0)
        print(
            f"{name:>12}: " + "  ".join(
                f"{op} {rate/1e3:7.1f}k/s" for op, rate in rates.items()
            ),
            file=sys.stderr,
        )
        if name == "tpu":
            tpu_rate = rates["check_and_update"]
        storage.close()

    # Disk get_counters over BASELINE config 3 shape (many namespaces,
    # one limit each): the scan re-attaches every stored key, exercising
    # the O(1) LimitKeyIndex path (was O(keys x limits) in round 2).
    disk = DiskStorage(tempfile.mkdtemp(prefix="bench-scan-") + "/c.db")
    scan_limits = [
        Limit(f"t{i}", 10**9, 60, [], ["u"]) for i in range(10_000)
    ]
    from limitador_tpu.core.counter import Counter

    for i, limit in enumerate(scan_limits):
        if i % 10 == 0:  # 1k live counters spread over the namespaces
            disk.update_counter(Counter(limit, {"u": "x"}), 1)
    t0 = time.perf_counter()
    found = disk.get_counters(set(scan_limits))
    dt = time.perf_counter() - t0
    print(
        f"disk get_counters: {len(found)} counters re-attached across "
        f"{len(scan_limits)} limits in {dt*1e3:.1f}ms",
        file=sys.stderr,
    )
    disk.close()
    emit("backend_check_and_update_per_sec", tpu_rate, "decisions/s", 1e7)


def bench_tenants(device_step):
    """Config 3: 10k namespaces x 100 keys, mixed windows, on device."""
    rng = np.random.default_rng(7)
    n_keys = 10_000 * 100
    batch = 1 << 15
    n_batches = 32
    keys = rng.integers(0, n_keys, (n_batches, batch)).astype(np.int32)
    windows = (
        rng.choice([1_000, 60_000, 3_600_000], batch).astype(np.int32)
    )
    rate = device_step(n_keys, keys, windows=windows)
    print(f"multi-tenant device: {rate/1e6:.2f}M decisions/s", file=sys.stderr)
    emit("tenants_decisions_per_sec", rate, "decisions/s", 1e7)


def bench_sharded():
    """Config 5: 10M keys sharded across local devices (virtual mesh
    off-TPU; on a real pod this rides ICI), swept over DEVICE COUNT so
    the artifact shows whether sharding actually scales (a single
    full-mesh number can hide negative scaling).

    Per device count k: a fill phase populates the k-shard table, then
    timed batches of 8192 decisions PER SHARD per launch (weak scaling —
    each shard's staging row carries a full micro-batch, which is how
    the serving batcher actually feeds the mesh) run the COLLECTIVE-LEAN
    path — owner-sharded hits, shard-local request ids, no psum/pmin —
    which is the hot path the storage stages for single-counter traffic.
    The fully coupled psum+pmin variant rides along at full width as
    ``sharded_global_decisions_per_sec`` (the price of a global-
    namespace batch, trend-tracked, not the headline).
    ``sharded_scaling_efficiency`` = rate(all devices) / rate(1 device):
    > 1.0 means adding shards now adds throughput."""
    import jax

    from limitador_tpu.device import enable_compile_cache, require_accelerator
    from limitador_tpu.parallel import (
        batch_sharding, make_mesh, make_sharded_table,
        sharded_check_and_update,
    )

    # Chips when the host has them; the virtual CPU mesh only when the
    # caller declares it (JAX_PLATFORMS=cpu + a forced host device
    # count, as the default run's matrix does). Either way the row says
    # which in sharded_platform.
    enable_compile_cache()
    require_accelerator("bench --config sharded")
    devices = jax.devices()
    n_dev = len(devices)
    local_cap = 1 << 21
    per_shard_h = 1 << 13  # 8192 decisions per shard per launch
    batches = 12
    rng = np.random.default_rng(3)

    def run_mesh(k: int, coupled_global: bool = False):
        """Rate over a k-device mesh; lean path unless coupled_global."""
        mesh = make_mesh(devices[:k])
        sharding = batch_sharding(mesh)
        state = make_sharded_table(mesh, local_cap)
        H_fill = 1 << 16
        fill = {
            "deltas": np.ones((k, H_fill), np.int32),
            "maxes": np.full((k, H_fill), 10**9, np.int32),
            "windows_ms": np.full((k, H_fill), 3_600_000, np.int32),
            "req_ids": np.broadcast_to(
                np.arange(H_fill, dtype=np.int32), (k, H_fill)
            ).copy(),
            "fresh": np.zeros((k, H_fill), bool),
            "bucket": np.zeros((k, H_fill), bool),
            "is_global": np.zeros((k, H_fill), bool),
        }
        fill = {
            key: jax.device_put(arr, sharding) for key, arr in fill.items()
        }
        # Fill: sequential distinct slots per shard — k x 65536 x 20
        # live counters (10.5M at k=8) before anything is timed.
        for b in range(20):
            base = b * H_fill
            fill_slots = jax.device_put(
                np.broadcast_to(
                    np.arange(base, base + H_fill, dtype=np.int32)
                    % local_cap,
                    (k, H_fill),
                ).copy(),
                sharding,
            )
            state, res = sharded_check_and_update(
                mesh, state, fill_slots, fill["deltas"], fill["maxes"],
                fill["windows_ms"], fill["req_ids"], fill["fresh"],
                fill["bucket"], fill["is_global"], np.int32(100),
                coupled=False, has_global=False,
            )
        jax.block_until_ready(res.admitted)

        H = per_shard_h
        # Timed draws stay inside the filled range so every hit lands on
        # a live counter (10M+ resident, a random subset hot per batch).
        slots = rng.integers(
            1024, 20 * H_fill, (batches, k, H)
        ).astype(np.int32)
        deltas = np.ones((k, H), np.int32)
        maxes = np.full((k, H), 1000, np.int32)
        windows = np.full((k, H), 60_000, np.int32)
        fresh = np.zeros((k, H), bool)
        bucket = np.zeros((k, H), bool)
        is_global = np.zeros((k, H), bool)
        if coupled_global:
            req = np.arange(k * H, dtype=np.int32).reshape(k, H)
            is_global[:, 0] = True
            slots[:, :, 0] = 7
        else:
            req = np.broadcast_to(
                np.arange(H, dtype=np.int32), (k, H)
            ).copy()
        consts = [
            jax.device_put(a, sharding)
            for a in (deltas, maxes, windows, req, fresh, bucket, is_global)
        ]
        staged = [jax.device_put(slots[i], sharding) for i in range(batches)]
        jax.block_until_ready(consts + staged)
        state, res = sharded_check_and_update(
            mesh, state, staged[0], *consts, np.int32(500),
            coupled=coupled_global, has_global=coupled_global,
        )
        jax.block_until_ready(res.admitted)
        rate = 0.0
        for _rep in range(2):  # best-of-two: box jitter
            t0 = time.perf_counter()
            for i in range(batches):
                state, res = sharded_check_and_update(
                    mesh, state, staged[i], *consts,
                    np.int32(1000 + _rep * 100 + i),
                    coupled=coupled_global, has_global=coupled_global,
                )
            jax.block_until_ready(res.admitted)
            rate = max(rate, batches * k * H / (time.perf_counter() - t0))
        return rate

    by_devices = {}
    for k in (1, 2, 4, 8):
        if k > n_dev:
            continue
        by_devices[str(k)] = round(run_mesh(k), 1)
        print(
            f"sharded lean over {k} device(s): "
            f"{by_devices[str(k)]/1e6:.2f}M decisions/s",
            file=sys.stderr,
        )
    full_k = max(int(k) for k in by_devices)
    rate = by_devices[str(full_k)]
    efficiency = round(rate / by_devices["1"], 3) if "1" in by_devices else 0.0
    global_rate = run_mesh(full_k, coupled_global=True)
    print(
        f"sharded over {full_k} devices: {rate/1e6:.2f}M decisions/s lean "
        f"(scaling efficiency {efficiency}x vs 1 device), "
        f"{global_rate/1e6:.2f}M decisions/s with psum+pmin coupling",
        file=sys.stderr,
    )
    emit(
        "sharded_decisions_per_sec", rate, "decisions/s", 1e7,
        sharded_platform=f"{devices[0].platform}-mesh-{n_dev}",
        sharded_by_devices=by_devices,
        sharded_scaling_efficiency=efficiency,
        sharded_global_decisions_per_sec=round(global_rate, 1),
    )


def _bench_pod_worker(args):
    """One process of the pod sweep (spawned by ``bench_pod``): forms
    the pod, owns one CPU shard, and measures

    - phase B (headline): decisions/s of owned-key ``check_many``
      batches through its host-local sharded device lane — the path
      routed ingress traffic actually rides, routing memo included;
    - phase A (p > 1): the routed frontend over real PeerLanes with
      round-robin arrivals — the locally-owned vs forwarded split
      (``pod_routed_share``) and the peer hop's p99 — then a second
      pass under ring-hash arrivals (an upstream that learned
      ``GET /debug/pod/routing``), whose share is the
      above-the-1/N-floor evidence (ISSUE 13);
    - phase C (ISSUE 13): the shard-aware native hot lane — per-host
      zero-Python engine throughput on locally-owned repeats, timed
      host-by-host with a PLAIN single-host pipeline interleaved in
      the same solo window (their ratio is the acceptance field: box
      sharing cancels, what remains is what shard-awareness costs),
      plus a mixed round-robin drive that exercises the C ownership
      split and the bulk-forward lane.
    """
    import asyncio
    import os

    import jax

    jax.config.update("jax_platforms", "cpu")
    from limitador_tpu import Context, Limit, RateLimiter, native
    from limitador_tpu.core.counter import Counter
    from limitador_tpu.parallel import initialize_pod, make_mesh, pod_barrier
    from limitador_tpu.routing import PodRouter, PodTopology, counter_key
    from limitador_tpu.tpu.sharded import TpuShardedStorage
    from limitador_tpu.tpu.storage import _Request

    p, pid = args.pod_worker_procs, args.pod_worker_id
    if p > 1:
        initialize_pod(args.pod_coordinator, p, pid)
    topo = PodTopology(
        hosts=p, host_id=pid, shards_per_host=jax.local_device_count()
    )
    storage = TpuShardedStorage(
        mesh=make_mesh(jax.local_devices()),
        local_capacity=1 << 16,
        global_region=256,
    )
    limiter = RateLimiter(storage)
    limit = Limit("bench", 10**9, 3600, [], ["k"], name="bench")
    limiter.add_limit(limit)

    n_keys = 4096
    counters = [
        Counter.new(limit, Context({"k": f"key-{i}"}))
        for i in range(n_keys)
    ]
    owned = [
        c for c in counters if topo.owner_host(counter_key(c)) == pid
    ]

    # -- phase B: owned-key device-lane throughput ---------------------------
    B = 512
    reqs = [
        [_Request([owned[(b * B + i) % len(owned)]], 1, False)
         for i in range(B)]
        for b in range(8)
    ]
    for batch in reqs[:2]:  # warm: slots allocated, programs compiled
        storage.check_many(batch)
    decided = 0
    rate = 0.0
    for _rep in range(2):  # best-of-two: box jitter
        t0 = time.perf_counter()
        for batch in reqs:
            storage.check_many(batch)
        dt = time.perf_counter() - t0
        decided = len(reqs) * B
        rate = max(rate, decided / dt)

    # -- phase A: routed frontend share + peer hop cost ----------------------
    routed = {"pod_routed_local": 0, "pod_routed_forwarded": 0,
              "pod_routed_pinned": 0}
    ringhash = dict(routed)
    peer_p99_ms = 0.0
    resilience = {"pod_failover_degraded_decisions": 0,
                  "pod_failover_seconds": 0.0}
    if p > 1:
        from limitador_tpu.server.peering import (
            PeerLane,
            PodFrontend,
            PodResilience,
        )

        ports = [int(x) for x in args.pod_peer_ports.split(",")]
        # The server's default resilience posture (degraded-owner
        # failover on), so pod_degraded_share / pod_failover_seconds
        # measure the shipped configuration: 0.0 on a healthy sweep,
        # nonzero when the sweep itself tripped a peer breaker.
        resilience = PodResilience()
        lane = PeerLane(
            pid,
            f"127.0.0.1:{ports[pid]}",
            {i: f"127.0.0.1:{port}" for i, port in enumerate(ports)
             if i != pid},
            None,
            resilience=resilience,
        )
        lane.start()
        frontend = PodFrontend(
            limiter, PodRouter(topo), lane, resilience=resilience
        )
        loop = asyncio.new_event_loop()
        # Warm the single-request program BEFORE peers start
        # forwarding: a forwarded decision must never pay this
        # worker's first-launch XLA compile inside the peer deadline.
        # _local_check (not the routed surface): the warm key must not
        # dial a lane that may not be serving yet.
        warm_key = owned[0].set_variables["k"]
        loop.run_until_complete(frontend._local_check(
            "bench", Context({"k": warm_key}), 0, False
        ))
        pod_barrier("bench-pod-lanes-ready")

        async def drive():
            # Round-robin arrivals over the shared key sequence: the
            # 1/p of keys this worker ingresses but does not own pay
            # the one forwarding hop.
            for i in range(pid, 512, p):
                ctx = Context({"k": f"key-{i % n_keys}"})
                await frontend.check_rate_limited_and_update(
                    "bench", ctx, 1, False
                )

        loop.run_until_complete(drive())
        pod_barrier("bench-pod-drive-done")
        routed = frontend.router.stats()

        async def drive_ringhash():
            # The upstream this PR teaches (ISSUE 13): an LB that
            # learned GET /debug/pod/routing — or approximates it with
            # Envoy ring-hash on descriptor keys — lands ~90% of this
            # worker's arrivals on keys it owns; the residue models
            # ring drift and keys the LB hasn't learned. The routed
            # share under THIS drive is what the round-robin 1/p floor
            # is compared against in the bench row.
            for j in range(512):
                if j % 10 == 9:
                    ctx = Context({"k": f"key-{(j * 37 + pid) % n_keys}"})
                else:
                    k = owned[(j * 131) % len(owned)].set_variables["k"]
                    ctx = Context({"k": k})
                await frontend.check_rate_limited_and_update(
                    "bench", ctx, 1, False
                )

        loop.run_until_complete(drive_ringhash())
        pod_barrier("bench-pod-ringhash-done")
        after = frontend.router.stats()
        ringhash = {
            key: after[key] - routed[key]
            for key in ("pod_routed_local", "pod_routed_forwarded",
                        "pod_routed_pinned")
        }
        peer_p99_ms = lane.stats()["pod_peer_p99_ms"]
        resilience = frontend.resilience_stats()
        # The federated view (ISSUE 12): rollups + this worker's hop
        # breakdown — the GET /debug/pod aggregate, embedded so pod
        # rounds record what the pod OBSERVED about itself, not just
        # what it decided. Give one exchange cadence a chance to land
        # a peer column first (best-effort; a timeout records the
        # local-only view, which is itself evidence).
        deadline = time.perf_counter() + 3.0
        while (
            not frontend.aggregator.peer_hosts()
            and time.perf_counter() < deadline
        ):
            time.sleep(0.05)
        pod_debug = frontend.pod_debug()
        pod_events = frontend.events.counts()
        lane.stop()
    else:
        pod_debug = {}
        pod_events = {}

    # -- phase C: shard-aware native hot lane (ISSUE 13) ---------------------
    native_rate = 0.0
    plain_rate = 0.0
    hot = {}
    bulk = {}
    native_note = ""
    if native.available() and native.pod_available():
        from limitador_tpu.server.proto import rls_pb2
        from limitador_tpu.tpu import AsyncTpuStorage, TpuStorage
        from limitador_tpu.tpu.native_pipeline import NativeRlsPipeline
        from limitador_tpu.tpu.pipeline import CompiledTpuLimiter

        api_limit = Limit(
            "api", 10**9, 3600, [], ["descriptors[0].u"], name="api"
        )

        def blob_of(u: int) -> bytes:
            req = rls_pb2.RateLimitRequest(domain="api")
            d = req.descriptors.add()
            e = d.entries.add()
            e.key = "u"
            e.value = f"user-{u}"
            return req.SerializeToString()

        # Constant per-host working set across sweep sizes: the first
        # 1024 users THIS host owns (at p=1 that is just the first
        # 1024), repeated 8x. Locally-owned repeats ride hp_hot_begin
        # end to end — the acceptance ratio's numerator, and at p=1
        # its single-host-baseline denominator.
        own_users = []
        u = 0
        while len(own_users) < 1024:
            c = Counter(api_limit, {"descriptors[0].u": f"user-{u}"})
            if topo.owner_host(counter_key(c)) == pid:
                own_users.append(u)
            u += 1
        owned_blobs = [blob_of(x) for x in own_users] * 8

        # The plain single-host native lane, living side by side with
        # the pod-wired one: the acceptance ratio interleaves timed
        # passes over BOTH in the same solo window, so box sharing
        # (p simulated hosts on one box's cores) cancels out and the
        # ratio isolates what shard-awareness itself costs — the same
        # same-process interleaved-ratio idiom every bench speedup in
        # this repo uses.
        plain_limiter = CompiledTpuLimiter(
            AsyncTpuStorage(TpuStorage(capacity=1 << 16), max_delay=0.001)
        )
        plain_limiter.add_limit(api_limit)
        p_plain = NativeRlsPipeline(
            plain_limiter, None, max_delay=0.001, hot_lane=True
        )

        n_limiter = CompiledTpuLimiter(
            AsyncTpuStorage(TpuStorage(capacity=1 << 16), max_delay=0.001)
        )
        n_lane = None
        if p > 1:
            # PeerLane/PodFrontend already imported by phase A (p > 1)
            nports = [int(x) for x in args.pod_native_ports.split(",")]
            n_lane = PeerLane(
                pid,
                f"127.0.0.1:{nports[pid]}",
                {i: f"127.0.0.1:{port}" for i, port in enumerate(nports)
                 if i != pid},
                None,
            )
            n_lane.start()
            n_frontend = PodFrontend(n_limiter, PodRouter(topo), n_lane)
            asyncio.run(n_frontend.configure_with([api_limit]))
            pipeline = NativeRlsPipeline(
                n_frontend, None, max_delay=0.001, hot_lane=True
            )
            n_frontend.attach_pipeline(pipeline)
        else:
            n_limiter.add_limit(api_limit)
            pipeline = NativeRlsPipeline(
                n_limiter, None, max_delay=0.001, hot_lane=True
            )

        # warm: derive + mirror + owner-stamp every owned plan, compile
        pipeline.decide_many(owned_blobs, chunk=len(owned_blobs))
        p_plain.decide_many(owned_blobs, chunk=len(owned_blobs))
        if p > 1:
            pod_barrier("bench-pod-native-ready")
        # Timed host-by-host: the p simulated hosts share THIS box's
        # cores, so concurrent timing would record CPU contention a
        # real pod (one box per host) doesn't have. Peers idle at the
        # barrier while one host times; within the window, pod-wired
        # and plain passes interleave (best-of-3 each) so their ratio
        # is same-window, same-box.
        def timed(pipe) -> float:
            t0 = time.perf_counter()
            n = len(pipe.decide_many(owned_blobs, chunk=len(owned_blobs)))
            return n / (time.perf_counter() - t0)

        for host in range(p):
            if host == pid:
                for _rep in range(3):
                    plain_rate = max(plain_rate, timed(p_plain))
                    native_rate = max(native_rate, timed(pipeline))
            if p > 1:
                pod_barrier(f"bench-pod-native-timed-{host}")
        if p > 1:
            # Mixed round-robin arrivals over a shared user range:
            # foreign-owned repeats classify in C and leave in bulk
            # forwards (one RPC per owner per chunk); pass 1 derives +
            # stamps, pass 2 rides the stamps. The local/foreign split
            # and bulk batch sizes are diffed over just these passes.
            mixed = [blob_of(x) for x in range(pid, 2048, p)] * 2
            base_stats = pipeline.pod_stats()
            pipeline.decide_many(mixed, chunk=4096)
            pipeline.decide_many(mixed, chunk=4096)
            pod_barrier("bench-pod-native-drive-done")
            now_stats = pipeline.pod_stats()
            hot = {
                k: now_stats[k] - base_stats.get(k, 0) for k in now_stats
            }
            ls = n_lane.stats()
            bulk = {k: ls[k] for k in (
                "pod_bulk_forward_batches", "pod_bulk_forward_rows",
                "pod_bulk_served_rows",
            )}
            n_lane.stop()
    else:
        native_note = native.build_error() or "pod ownership exports absent"

    with open(args.pod_out, "w") as f:
        json.dump({
            "rate": rate,
            "decided": decided,
            "owned_keys": len(owned),
            "routed": routed,
            "ringhash": ringhash,
            "peer_p99_ms": peer_p99_ms,
            "resilience": resilience,
            "route_memo": storage.launch_stats(),
            "pod_debug": pod_debug,
            "pod_events": pod_events,
            "native_rate": native_rate,
            "plain_rate": plain_rate,
            "hot": hot,
            "bulk": bulk,
            **({"native_note": native_note} if native_note else {}),
        }, f)
    return 0


def bench_pod():
    """Pod sweep (ISSUE 10): 1/2/4-process `jax.distributed` CPU pods
    on THIS box (one shard per process), emitting
    ``pod_decisions_per_sec`` (summed owned-key device-lane throughput),
    ``pod_scaling_efficiency`` (rate at max processes / rate at 1 — the
    same-run interleaved ratio, per the PR 5 box-variance caveat: the
    1/2/4 runs share one invocation and one box) and
    ``pod_routed_share`` (locally-owned fraction under round-robin
    arrivals, with the peer hop's p99 alongside). The fast-path variant
    (ISSUE 13) adds ``pod_native_engine_decisions_per_sec`` (summed
    shard-aware native-hot-lane rate, each host timed solo),
    ``pod_native_per_host_ratio`` (pod-wired vs plain single-host lane
    interleaved in the same solo windows — the within-10% acceptance
    field),
    ``pod_hot_local_share`` + ``pod_bulk_forward`` (the C lane's
    local/foreign split and bulk-RPC amortization under round-robin
    arrivals) and ``pod_routed_share_ringhash`` (the share when an
    upstream has learned ``GET /debug/pod/routing``). Every row carries
    the pod topology."""
    import os
    import subprocess
    import tempfile

    by_processes = {}
    shares = {}
    ringhash_shares = {}
    native_by_processes = {}
    native_vs_plain = {}
    hot_shares = {}
    bulk_by_p = {}
    peer_p99 = {}
    degraded_shares = {}
    failover_seconds = {}
    pod_debug_by_p = {}
    pod_note = ""
    native_note = ""
    for p in (1, 2, 4):
        coordinator = f"127.0.0.1:{_free_port()}"
        peer_ports = ",".join(str(_free_port()) for _ in range(p))
        native_ports = ",".join(str(_free_port()) for _ in range(p))
        env = {
            k: v for k, v in os.environ.items()
            if not k.startswith("TPU_POD_")
        }
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
        with tempfile.TemporaryDirectory() as tmp:
            procs = []
            outs = []
            for pid in range(p):
                out = os.path.join(tmp, f"pod-{pid}.json")
                outs.append(out)
                procs.append(subprocess.Popen(
                    [sys.executable, os.path.abspath(__file__),
                     "--config", "pod",
                     "--pod-worker-id", str(pid),
                     "--pod-worker-procs", str(p),
                     "--pod-coordinator", coordinator,
                     "--pod-peer-ports", peer_ports,
                     "--pod-native-ports", native_ports,
                     "--pod-out", out],
                    env=env, stdout=subprocess.DEVNULL,
                    stderr=subprocess.PIPE, text=True,
                ))
            failed = None
            for pid, proc in enumerate(procs):
                try:
                    _out, err = proc.communicate(timeout=600)
                except subprocess.TimeoutExpired:
                    failed = f"{p}-process pod timed out"
                    break
                if proc.returncode != 0:
                    failed = (
                        f"{p}-process pod worker {pid} rc="
                        f"{proc.returncode}: {err.strip()[-400:]}"
                    )
                    break
            if failed:
                # One dead worker dooms the pod: kill the rest NOW so
                # zombies can't starve (or key-collide with) the next
                # sweep size.
                for x in procs:
                    if x.poll() is None:
                        x.kill()
                for x in procs:
                    try:
                        x.wait(timeout=30)
                    except subprocess.TimeoutExpired:
                        pass
            if failed:
                print(f"bench_pod: {failed}", file=sys.stderr)
                pod_note = failed
                continue
            rate = 0.0
            local = forwarded = pinned = degraded = 0
            ring_local = ring_total = 0
            native_rate = plain_rate = 0.0
            hot_local = hot_foreign = 0
            bulk_batches = bulk_rows = bulk_served = 0
            p99 = failover_s = 0.0
            for out in outs:
                with open(out) as f:
                    r = json.load(f)
                rate += r["rate"]
                local += r["routed"]["pod_routed_local"]
                forwarded += r["routed"]["pod_routed_forwarded"]
                pinned += r["routed"]["pod_routed_pinned"]
                ring = r.get("ringhash", {})
                ring_local += ring.get("pod_routed_local", 0)
                ring_total += sum(ring.values())
                native_rate += r.get("native_rate", 0.0)
                plain_rate += r.get("plain_rate", 0.0)
                hot = r.get("hot", {})
                hot_local += hot.get("pod_hot_local_rows", 0)
                hot_foreign += hot.get("pod_hot_foreign_rows", 0)
                b = r.get("bulk", {})
                bulk_batches += b.get("pod_bulk_forward_batches", 0)
                bulk_rows += b.get("pod_bulk_forward_rows", 0)
                bulk_served += b.get("pod_bulk_served_rows", 0)
                if r.get("native_note"):
                    native_note = r["native_note"]
                p99 = max(p99, r["peer_p99_ms"])
                res = r.get("resilience", {})
                degraded += int(
                    res.get("pod_failover_degraded_decisions", 0)
                )
                failover_s += float(res.get("pod_failover_seconds", 0.0))
                # the federated view of the last multi-process sweep
                # (ISSUE 12): worker 0's GET /debug/pod aggregate —
                # rollups + hop breakdown — rides the row
                if p > 1 and r.get("pod_debug"):
                    pod_debug_by_p[str(p)] = {
                        "rollups": r["pod_debug"].get("rollups", {}),
                        "hosts": sorted(r["pod_debug"].get("hosts", {})),
                        "hops": r["pod_debug"].get("hops", {}),
                        "events": r.get("pod_events", {}),
                    }
        by_processes[str(p)] = round(rate, 1)
        native_by_processes[str(p)] = round(native_rate, 1)
        if plain_rate:
            # THE acceptance ratio (ISSUE 13): pod-wired vs plain
            # single-host native lane, interleaved in the same solo
            # timing window of the same processes — box sharing
            # cancels, what remains is what shard-awareness costs.
            native_vs_plain[str(p)] = round(native_rate / plain_rate, 3)
        total_routed = local + forwarded + pinned
        if total_routed:
            shares[str(p)] = round(local / total_routed, 4)
            # Resilience evidence (ISSUE 11): the share of routed
            # decisions served by a degraded-owner stand-in, and the
            # cumulative breaker-away-from-closed clock. 0.0 on a
            # healthy sweep — nonzero means the sweep itself tripped.
            degraded_shares[str(p)] = round(degraded / total_routed, 4)
        # Fast-path evidence (ISSUE 13): the routed share an ownership-
        # aware upstream achieves (vs the 1/p round-robin floor), the C
        # lane's local/foreign row split under round-robin arrivals,
        # and how many rows each bulk-forward RPC amortized.
        if ring_total:
            ringhash_shares[str(p)] = round(ring_local / ring_total, 4)
        if hot_local + hot_foreign:
            hot_shares[str(p)] = round(
                hot_local / (hot_local + hot_foreign), 4
            )
        if bulk_batches:
            bulk_by_p[str(p)] = {
                "batches": bulk_batches,
                "rows": bulk_rows,
                "served_rows": bulk_served,
                "mean_batch": round(bulk_rows / bulk_batches, 2),
            }
        peer_p99[str(p)] = round(p99, 3)
        failover_seconds[str(p)] = round(failover_s, 3)
        print(
            f"pod over {p} process(es): {rate/1e3:.1f}k decisions/s, "
            f"native hot lane {native_rate/1e3:.1f}k/s"
            + (
                f", routed share {shares[str(p)]:.2%} local "
                f"(ring-hash {ringhash_shares.get(str(p), 0.0):.2%}), "
                f"peer p99 {p99:.1f}ms" if p > 1 and total_routed else ""
            ),
            file=sys.stderr,
        )
    if "1" not in by_processes:
        sys.exit("bench_pod: no successful pod run")
    full_p = max(int(k) for k in by_processes)
    rate = by_processes[str(full_p)]
    efficiency = round(rate / by_processes["1"], 3)
    routed_share = shares.get(str(full_p), 1.0)
    # The acceptance ratio (ISSUE 13): pod-wired hot-lane throughput vs
    # the plain single-host native lane on locally-owned traffic,
    # interleaved in the same solo timing windows (see worker phase C).
    # ~1.0 means pod mode stopped costing the fast path; the 10%
    # criterion reads this field. The cross-sweep per-host rate
    # (native_by_processes[p] / p vs [1]) additionally carries the
    # p-simulated-hosts-on-one-box CPU contention a real pod doesn't.
    native_full = native_by_processes.get(str(full_p), 0.0)
    native_per_host_ratio = native_vs_plain.get(str(full_p), 0.0)
    emit(
        "pod_decisions_per_sec", rate, "decisions/s", 1e6,
        pod_by_processes=by_processes,
        pod_processes=full_p,
        pod_scaling_efficiency=efficiency,
        pod_routed_share=routed_share,
        pod_routed_share_by_processes=shares,
        pod_routed_share_ringhash=ringhash_shares.get(str(full_p), 0.0),
        pod_routed_share_ringhash_by_processes=ringhash_shares,
        pod_native_engine_decisions_per_sec=native_full,
        pod_native_by_processes=native_by_processes,
        pod_native_per_host_ratio=native_per_host_ratio,
        pod_native_vs_plain_by_processes=native_vs_plain,
        pod_hot_local_share=hot_shares.get(str(full_p), 0.0),
        pod_hot_local_share_by_processes=hot_shares,
        pod_bulk_forward=bulk_by_p.get(str(full_p), {}),
        pod_peer_p99_ms_by_processes=peer_p99,
        pod_degraded_share=degraded_shares.get(str(full_p), 0.0),
        pod_failover_seconds=failover_seconds.get(str(full_p), 0.0),
        pod_debug=pod_debug_by_p.get(str(full_p), {}),
        **({"pod_note": pod_note} if pod_note else {}),
        **({"pod_native_note": native_note} if native_note else {}),
    )
    bench_pod_resize()
    bench_pod_join()


def bench_pod_resize():
    """Elastic-pod resize row (ISSUE 15): decisions/sec and p99 sampled
    BEFORE / DURING / AFTER a live 2->4 membership transition on an
    in-process mini-pod (InMemory frontends over real gRPC peer lanes —
    the resize control/migration plane is pure host code by design, so
    this measures the machinery itself, not a device). The row embeds
    ``pod_resize_seconds`` (wall time of the transition) and
    ``pod_routed_share_recovery_s`` — how long after ``resize_end`` the
    ring-hash-routed local share takes to return to >=0.9 of its
    pre-resize value (the acceptance criterion's convergence clock)."""
    import asyncio
    import threading

    try:
        import grpc  # noqa: F401
    except ImportError:
        print("bench_pod_resize: grpc unavailable, skipped",
              file=sys.stderr)
        return
    from limitador_tpu import Context, Limit, RateLimiter
    from limitador_tpu.routing import PodRouter, PodTopology
    from limitador_tpu.server.peering import (
        PeerLane,
        PodFrontend,
        PodResilience,
    )
    from limitador_tpu.server.resize import PodResizeCoordinator
    from limitador_tpu.storage.in_memory import InMemoryStorage

    n_full = 4
    ports = [_free_port() for _ in range(n_full)]
    addrs = {h: f"127.0.0.1:{ports[h]}" for h in range(n_full)}
    limits = [Limit("bench_resize", 1 << 30, 3600, [], ["u"], name="u")]
    lanes, fronts = [], []
    for host in range(n_full):
        member = host < 2
        cfg = PodResilience(
            degraded=True, retry=True, breaker_failures=2,
            breaker_reset_s=0.2, probe_interval_s=0.2,
        )
        lane = PeerLane(
            host, addrs[host],
            {o: addrs[o] for o in range(2) if member and o != host},
            None, resilience=cfg,
        )
        lane.start()
        front = PodFrontend(
            RateLimiter(InMemoryStorage(65536)),
            PodRouter(PodTopology(
                hosts=2 if member else n_full, host_id=host,
                shards_per_host=1,
            )),
            lane, resilience=cfg,
        )
        coordinator = PodResizeCoordinator(
            front,
            peers={
                h: addrs[h] for h in (range(2) if member else (host,))
            },
            listen_address=addrs[host],
        )
        front.attach_resize(coordinator)
        asyncio.run(front.configure_with(limits))
        lanes.append(lane)
        fronts.append(front)
    users = [f"u{i}" for i in range(256)]
    # ring-hash arrivals: each user lands at its CURRENT owner (what an
    # upstream that learned GET /debug/pod/routing would do)
    phase_stats = {}

    def drive(tag, seconds, hosts):
        lat = []
        n = 0
        loop_deadline = time.perf_counter() + seconds
        while time.perf_counter() < loop_deadline:
            user = users[n % len(users)]
            ctx = Context({"u": user})
            front = fronts[n % hosts]
            t0 = time.perf_counter()
            asyncio.run(front.check_rate_limited_and_update(
                "bench_resize", ctx, 1, False
            ))
            lat.append(time.perf_counter() - t0)
            n += 1
        lat.sort()
        phase_stats[tag] = {
            "decisions_per_sec": round(n / seconds, 1),
            "p99_ms": round(
                lat[int(0.99 * (len(lat) - 1))] * 1e3, 3
            ) if lat else 0.0,
        }

    drive("before", 1.0, 2)
    resize_out = {}

    def run_resize():
        try:
            resize_out.update(fronts[0].resize.resize(
                n_full, peers={h: addrs[h] for h in range(n_full)}
            ))
        except Exception as exc:
            resize_out["error"] = f"{exc}"

    t_resize = threading.Thread(target=run_resize, daemon=True)
    t0 = time.perf_counter()
    t_resize.start()
    drive("during", 1.0, 2)  # arrivals keep hitting the old ingresses
    t_resize.join(timeout=60)
    resize_s = time.perf_counter() - t0
    transition = resize_out.get("transition") or {}
    if transition.get("seconds"):
        # the headline is the transition's own wall time; the thread
        # join above also absorbed the interleaved "during" drive
        resize_s = float(transition["seconds"])
    # routed-share recovery: drive ring-hash arrivals on the new
    # topology until the local share is back over 0.9
    recovery_s = None
    t_rec = time.perf_counter()
    for _ in range(50):
        before_stats = [f.router.stats() for f in fronts]
        for user in users:
            key = (limits[0]._identity, (("u", user),))
            owner = fronts[0].router.topology.owner_host(key)
            front = fronts[owner if owner < len(fronts) else 0]
            asyncio.run(front.check_rate_limited_and_update(
                "bench_resize", Context({"u": user}), 1, False
            ))
        after_stats = [f.router.stats() for f in fronts]
        local = sum(
            a["pod_routed_local"] - b["pod_routed_local"]
            for a, b in zip(after_stats, before_stats)
        )
        total = sum(
            sum(a[k] - b[k] for k in (
                "pod_routed_local", "pod_routed_forwarded",
                "pod_routed_pinned",
            ))
            for a, b in zip(after_stats, before_stats)
        )
        if total and local / total >= 0.9:
            recovery_s = round(time.perf_counter() - t_rec, 3)
            break
    drive("after", 1.0, n_full)
    for lane in lanes:
        lane.stop()
    ok = bool(resize_out.get("ok"))
    emit(
        "pod_resize_seconds", resize_s, "s", 1.0, ndigits=3,
        lower_is_better=True,
        pod_resize_ok=ok,
        pod_resize_hosts="2->4",
        pod_resize_phases=phase_stats,
        pod_resize_transition=resize_out.get("transition"),
        pod_routed_share_recovery_s=recovery_s,
        pod_resize_stats=fronts[0].resize.stats(),
        **(
            {"pod_resize_error": resize_out["error"]}
            if "error" in resize_out else {}
        ),
    )
    print(
        f"pod resize 2->4: {'ok' if ok else 'FAILED'} in {resize_s:.2f}s, "
        f"before {phase_stats['before']['decisions_per_sec']/1e3:.1f}k/s "
        f"p99 {phase_stats['before']['p99_ms']:.1f}ms, during "
        f"{phase_stats['during']['decisions_per_sec']/1e3:.1f}k/s p99 "
        f"{phase_stats['during']['p99_ms']:.1f}ms, after "
        f"{phase_stats['after']['decisions_per_sec']/1e3:.1f}k/s p99 "
        f"{phase_stats['after']['p99_ms']:.1f}ms, routed-share recovery "
        f"{recovery_s}s",
        file=sys.stderr,
    )


def bench_pod_join():
    """Warm-standby join row (ISSUE 18): time-to-first-decision and
    time-to-routed-share-1 for a host joining a live 2-host in-process
    mini-pod (InMemory frontends over real gRPC peer lanes — like the
    resize row, this measures the membership machinery, not a device),
    cold vs warm. Both arms pay a REAL kernel warm-up
    (``WarmStandby.warm()`` jit-compiles the decision kernels on this
    box's backend); the warm arm pays it BEFORE the join clock starts,
    the cold arm inside the ttfd window — exactly the cost the standby
    design moves off the critical path. The PR 15 resize row
    (``pod_resize_seconds``) lands alongside in the same artifact as
    the membership-change baseline."""
    import asyncio
    import threading

    try:
        import grpc  # noqa: F401
    except ImportError:
        print("bench_pod_join: grpc unavailable, skipped",
              file=sys.stderr)
        return
    from limitador_tpu import Context, Limit, RateLimiter
    from limitador_tpu.routing import PodRouter, PodTopology
    from limitador_tpu.server.peering import (
        PeerLane,
        PodFrontend,
        PodResilience,
    )
    from limitador_tpu.server.resize import PodResizeCoordinator
    from limitador_tpu.server.standby import WarmStandby
    from limitador_tpu.storage.in_memory import InMemoryStorage

    limits = [Limit("bench_join", 1 << 30, 3600, [], ["u"], name="u")]
    users = [f"u{i}" for i in range(256)]

    def run_arm(warm_before):
        ports = [_free_port() for _ in range(3)]
        addrs = {h: f"127.0.0.1:{ports[h]}" for h in range(3)}
        lanes, fronts = [], []
        for host in range(3):
            member = host < 2
            cfg = PodResilience(
                degraded=True, retry=True, breaker_failures=2,
                breaker_reset_s=0.2, probe_interval_s=0.2,
            )
            lane = PeerLane(
                host if member else 0, addrs[host],
                {o: addrs[o] for o in range(2) if member and o != host},
                None, resilience=cfg,
            )
            lane.start()
            front = PodFrontend(
                RateLimiter(InMemoryStorage(65536)),
                PodRouter(PodTopology(
                    hosts=2 if member else 1,
                    host_id=host if member else 0,
                    shards_per_host=1,
                )),
                lane, resilience=cfg,
            )
            coordinator = PodResizeCoordinator(
                front,
                peers=(
                    {h: addrs[h] for h in range(2)} if member else {}
                ),
                listen_address=addrs[host],
            )
            front.attach_resize(coordinator)
            if member:
                asyncio.run(front.configure_with(limits))
            lanes.append(lane)
            fronts.append(front)
        # small kernel set keeps the bench quick; both arms compile the
        # SAME set so cold-vs-warm isolates placement, not workload
        standby = WarmStandby(
            fronts[2], fronts[2].resize, warm_buckets=(8, 16)
        )
        compile_s = None
        if warm_before:
            standby.warm()
            compile_s = standby.warm_seconds
        # a little pre-join traffic so the pod is live, not idle
        for user in users[:32]:
            asyncio.run(fronts[0].check_rate_limited_and_update(
                "bench_join", Context({"u": user}), 1, False
            ))
        t0 = time.perf_counter()
        out = fronts[0].resize.join_host(addrs[2])
        if not warm_before:
            # the compile a cold joiner pays before its first decision
            standby.warm()
            compile_s = standby.warm_seconds
        ttfd = None
        for user in users:
            key = (limits[0]._identity, (("u", user),))
            if fronts[0].router.topology.owner_host(key) != 2:
                continue
            asyncio.run(fronts[0].check_rate_limited_and_update(
                "bench_join", Context({"u": user}), 1, False
            ))
            ttfd = round(time.perf_counter() - t0, 3)
            break
        # routed-share-1: ring-hash arrivals on the NEW topology until
        # the pod-wide local share converges (the upstream re-learned
        # GET /debug/pod/routing and every key lands at its owner)
        share1_s = None
        for _ in range(50):
            before = [f.router.stats() for f in fronts]
            for user in users:
                key = (limits[0]._identity, (("u", user),))
                owner = fronts[0].router.topology.owner_host(key)
                asyncio.run(
                    fronts[owner].check_rate_limited_and_update(
                        "bench_join", Context({"u": user}), 1, False
                    )
                )
            after = [f.router.stats() for f in fronts]
            local = sum(
                a["pod_routed_local"] - b["pod_routed_local"]
                for a, b in zip(after, before)
            )
            total = sum(
                sum(a[k] - b[k] for k in (
                    "pod_routed_local", "pod_routed_forwarded",
                    "pod_routed_pinned",
                ))
                for a, b in zip(after, before)
            )
            if total and local / total >= 0.99:
                share1_s = round(time.perf_counter() - t0, 3)
                break
        joiner_stats = fronts[2].resize.stats()
        for lane in lanes:
            lane.stop()
        return {
            "ok": bool(out.get("ok")),
            "ttfd_s": ttfd,
            "time_to_routed_share_1_s": share1_s,
            "join_seconds": out.get("join_seconds"),
            "seeded": out.get("seeded"),
            "compile_s": compile_s,
            "joiner_ttfd_s": joiner_stats.get("join_ttfd_seconds"),
        }

    cold = run_arm(warm_before=False)
    warm = run_arm(warm_before=True)
    emit(
        "pod_join_ttfd_seconds", warm["ttfd_s"] or 0.0, "s", 1.0,
        ndigits=3, lower_is_better=True,
        pod_join_warm=warm,
        pod_join_cold=cold,
        pod_join_hosts="2->3",
        pod_join_warm_buckets=[8, 16],
        device_backed=device_backed(),
    )
    print(
        f"pod join 2->3: warm ttfd {warm['ttfd_s']}s "
        f"(routed-share-1 {warm['time_to_routed_share_1_s']}s, "
        f"{warm['seeded']} plans seeded), cold ttfd {cold['ttfd_s']}s "
        f"(compile {cold['compile_s']}s inside the window)",
        file=sys.stderr,
    )


def _free_port() -> int:
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


_BENCH_LIMITS_YAML = (
    "- namespace: api\n  max_value: 1000000000\n  seconds: 60\n"
    "  conditions: []\n  variables: [\"descriptors[0].u\"]\n"
)


def _write_limits_file() -> str:
    import tempfile

    f = tempfile.NamedTemporaryFile("w", suffix=".yaml", delete=False)
    f.write(_BENCH_LIMITS_YAML)
    f.close()
    return f.name


def _stderr_log_path() -> str:
    import tempfile

    f = tempfile.NamedTemporaryFile(
        "w", suffix=".log", prefix="bench-server-", delete=False
    )
    f.close()
    return f.name


def _spawn_server(argv, stderr_path: str, extra_env=None):
    """Launch a server subprocess with stderr captured to a FILE (a pipe
    nobody drains would deadlock a chatty server)."""
    import os
    import subprocess

    env = dict(os.environ, **extra_env) if extra_env else None
    with open(stderr_path, "w") as stderr_file:
        return subprocess.Popen(
            [sys.executable, "-m", "limitador_tpu.server"] + argv,
            stdout=subprocess.DEVNULL,
            stderr=stderr_file,
            env=env,
        )


def _wait_http(port, proc, stderr_path=None, tries=240):
    import urllib.request

    for _ in range(tries):
        if proc.poll() is not None:
            tail = ""
            if stderr_path:
                try:
                    with open(stderr_path) as f:
                        tail = f.read()[-1000:]
                except OSError:
                    pass
            raise RuntimeError(
                f"bench server on :{port} exited rc={proc.returncode}: "
                f"{tail}"
            )
        try:
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/status", timeout=1
            )
            return
        except Exception:
            time.sleep(0.5)
    raise RuntimeError(f"bench server on :{port} never came up")


def _native_rls_server(native_ingress=False, batch_delay_us=None,
                       extra_env=None, tries=480):
    """Context manager: boot a tpu/native-pipeline server for a serving
    bench, yield (rls_port, http_port, ok) and tear it down. Callers set
    ``ok[0] = True`` on success; a failed run keeps the server stderr
    file (the only server-side evidence) and prints its path."""
    import contextlib
    import os
    import subprocess

    @contextlib.contextmanager
    def ctx():
        limits_path = _write_limits_file()
        stderr_path = _stderr_log_path()
        rls_port, http_port = _free_port(), _free_port()
        server_args = [
            limits_path, "tpu", "--pipeline", "native",
            "--rls-port", str(rls_port), "--http-port", str(http_port),
        ]
        if batch_delay_us is not None:
            server_args += ["--batch-delay-us", str(batch_delay_us)]
        if native_ingress:
            server_args.append("--native-ingress")
        proc = _spawn_server(server_args, stderr_path, extra_env=extra_env)
        ok = [False]
        try:
            _wait_http(http_port, proc, stderr_path, tries=tries)
            if native_ingress:
                # The server falls back to Python gRPC on the same port
                # when the ingress can't start; recording that as
                # ingress_* would corrupt the comparison these numbers
                # exist to make.
                with open(stderr_path) as f:
                    if "native HTTP/2 ingress on" not in f.read():
                        raise RuntimeError(
                            "server did not start the native ingress "
                            f"(see {stderr_path})"
                        )
            yield rls_port, http_port, ok
        finally:
            # Wait for the child to EXIT, not for a port to close: the
            # chip is free for the next process only once this one is
            # gone.
            proc.terminate()
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
            os.unlink(limits_path)
            if ok[0]:
                try:
                    os.unlink(stderr_path)
                except OSError:
                    pass
            else:
                print(
                    f"server stderr kept at {stderr_path}", file=sys.stderr
                )

    return ctx()


def _hist_p99(buckets) -> float:
    """p99 by bucket interpolation over Prometheus-exposition
    (le, cumulative_count) pairs; None with no observations. The +Inf
    tail clamps to the last finite edge."""
    total = buckets[-1][1] if buckets else 0.0
    if total <= 0:
        return None
    target = 0.99 * total
    prev_le = prev_cum = 0.0
    for le, cum in buckets:
        if cum >= target:
            if le == float("inf"):
                return prev_le
            span = cum - prev_cum
            frac = (target - prev_cum) / span if span else 1.0
            return prev_le + (le - prev_le) * frac
        prev_le, prev_cum = le, cum
    return None


def _scrape_device_metrics(http_port: int) -> dict:
    """Read the device-plane batching telemetry off a serving process's
    /metrics exposition after a measured pass (observability/metrics.py
    batcher_* families): queue-wait p99 by histogram-bucket interpolation,
    mean batch fill ratio, and the share of flushes released by the
    linger deadline rather than a full batch — so BENCH rounds can
    correlate throughput with batching behavior."""
    import re
    import urllib.request

    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{http_port}/metrics", timeout=10
        ) as resp:
            text = resp.read().decode()
    except Exception as exc:
        print(f"device metrics scrape failed: {exc}", file=sys.stderr)
        return {}

    buckets = []  # (le_seconds, cumulative_count) in exposition order
    fill_sum = fill_count = 0.0
    flushes = {}
    # Native telemetry plane + SLO watchdog (observability/
    # native_plane.py): slo_* gauges verbatim, native_phase_* histogram
    # p99s by bucket interpolation — every serving bench row carries
    # the native-plane evidence (ISSUE 7 acceptance).
    slo = {}
    native_phase = {}  # family -> [(le_seconds, cumulative_count)]
    # Admission-plane signals (observability/metrics.py admission_*
    # families): sheds, breaker state, cumulative failed-over seconds.
    sheds = 0.0
    decided_calls = 0.0  # authorized + limited (the shed-rate base)
    breaker_state = None
    failover_seconds = None
    # Only the decision path: batcher="update" is the write-behind
    # queue, which lingers to its deadline by design and would skew
    # every derived figure.
    check = 'batcher="check"'
    for line in text.splitlines():
        if line.startswith("batcher_queue_wait_bucket") and check in line:
            m = re.search(r'le="([^"]+)"\}\s+([0-9.eE+-]+)', line)
            if m:
                le = float("inf") if m.group(1) == "+Inf" else float(m.group(1))
                buckets.append((le, float(m.group(2))))
        elif line.startswith("batcher_batch_fill_ratio_sum") and check in line:
            fill_sum = float(line.split()[-1])
        elif (line.startswith("batcher_batch_fill_ratio_count")
              and check in line):
            fill_count = float(line.split()[-1])
        elif line.startswith("batcher_flushes_total") and check in line:
            m = re.search(r'reason="([^"]+)"\}\s+([0-9.eE+-]+)', line)
            if m:
                flushes[m.group(1)] = float(m.group(2))
        elif line.startswith("admission_sheds_total"):
            sheds += float(line.split()[-1])
        elif line.startswith("admission_breaker_state "):
            breaker_state = float(line.split()[-1])
        elif line.startswith("admission_failover_seconds_total"):
            failover_seconds = float(line.split()[-1])
        elif (line.startswith("authorized_calls_total")
              or line.startswith("limited_calls_total")):
            decided_calls += float(line.split()[-1])
        elif line.startswith("slo_"):
            parts = line.split()
            if len(parts) == 2:
                try:
                    slo[parts[0]] = float(parts[1])
                except ValueError:
                    pass
        elif line.startswith("native_phase_") and "_bucket{" in line:
            fam = line.split("_bucket{", 1)[0]
            m = re.search(r'le="([^"]+)"\}\s+([0-9.eE+-]+)', line)
            if m:
                le = (
                    float("inf") if m.group(1) == "+Inf"
                    else float(m.group(1))
                )
                native_phase.setdefault(fam, []).append(
                    (le, float(m.group(2)))
                )

    out = {}
    # The unified ControlSignals snapshot (observability/signals.py):
    # GET /debug/signals serves the joined, timestamped vector — embed
    # it verbatim so every serving bench row carries the observation
    # plane (ISSUE 8 acceptance), plus the observatory's top tenants.
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{http_port}/debug/signals", timeout=10
        ) as resp:
            payload = json.loads(resp.read().decode())
        out["signals"] = payload.get("current", {})
    except Exception:
        pass  # pre-observatory server / host-only storage: no bus
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{http_port}/debug/top?k=5", timeout=10
        ) as resp:
            payload = json.loads(resp.read().decode())
        out["tenant_top"] = [
            {k: r.get(k) for k in ("namespace", "limit_name", "key",
                                   "hits", "utilization")}
            for r in payload.get("top", [])
        ]
    except Exception:
        pass
    if slo:
        out["slo"] = {k: round(v, 4) for k, v in sorted(slo.items())}
    phase_p99 = {}
    for fam, fam_buckets in sorted(native_phase.items()):
        p99_s = _hist_p99(fam_buckets)
        if p99_s is not None:
            phase_p99[fam[len("native_phase_"):]] = round(p99_s * 1e6, 2)
    if phase_p99:
        out["native_phase_p99_us"] = phase_p99
    if breaker_state is not None:
        # Only meaningful when the admission plane is on; a server
        # without it exposes no admission_* families at all.
        out["breaker_state"] = int(breaker_state)
        out["failover_seconds"] = round(failover_seconds or 0.0, 3)
        out["shed_total"] = int(sheds)
        if sheds + decided_calls > 0:
            out["shed_rate"] = round(sheds / (sheds + decided_calls), 4)
    total = buckets[-1][1] if buckets else 0.0
    if total > 0:
        target = 0.99 * total
        prev_le = prev_cum = 0.0
        for le, cum in buckets:
            if cum >= target:
                if le == float("inf"):
                    p99 = prev_le  # tail beyond the last finite bucket
                else:
                    span = cum - prev_cum
                    frac = (target - prev_cum) / span if span else 1.0
                    p99 = prev_le + (le - prev_le) * frac
                out["queue_wait_p99_ms"] = round(p99 * 1e3, 3)
                break
            prev_le, prev_cum = le, cum
    if fill_count > 0:
        out["batch_fill_ratio"] = round(fill_sum / fill_count, 4)
    # Shutdown-drain flushes are teardown, not steady-state behavior.
    decided = flushes.get("size", 0.0) + flushes.get("deadline", 0.0)
    if decided > 0:
        out["deadline_flush_share"] = round(
            flushes.get("deadline", 0.0) / decided, 4
        )
    return out


def grpc_closed_loop(concurrency: int = 64, per_worker: int = 250,
                     batch_delay_us: int = 200, native_ingress: bool = False):
    """End-to-end gRPC latency evidence: a real server process, a real
    socket, concurrent ShouldRateLimit — the closed-loop p50/p99 the 2ms
    target is judged against (BASELINE.json). Returns
    (rps, p50_ms, p99_ms, floor_p50_ms) where the floor is the same loop
    against an empty-domain request (no storage touched): pure
    ingress+loop+socket overhead, isolating the device share.
    ``native_ingress`` drives the vendored C++ HTTP/2 ingress instead of
    the Python grpc.aio server."""
    import asyncio

    import grpc

    from limitador_tpu.server.proto import rls_pb2

    with _native_rls_server(
        native_ingress=native_ingress, batch_delay_us=batch_delay_us
    ) as (rls_port, _http_port, ok):

        async def drive():
            channel = grpc.aio.insecure_channel(f"127.0.0.1:{rls_port}")
            method = channel.unary_unary(
                "/envoy.service.ratelimit.v3.RateLimitService"
                "/ShouldRateLimit",
                request_serializer=lambda m: m.SerializeToString(),
                response_deserializer=rls_pb2.RateLimitResponse.FromString,
            )

            def make_req(domain, user):
                req = rls_pb2.RateLimitRequest(domain=domain)
                d = req.descriptors.add()
                e = d.entries.add()
                e.key = "u"
                e.value = user
                return req

            reqs = [make_req("api", f"user-{i}") for i in range(512)]
            floor_req = make_req("", "x")  # empty domain: no storage

            async def worker(n, req_of, out):
                for i in range(n):
                    t0 = time.perf_counter()
                    await method(req_of(i))
                    out.append(time.perf_counter() - t0)

            # Warmup: compiles kernel buckets, fills the slot table.
            warm = []
            await asyncio.gather(*[
                worker(30, lambda i, w=w: reqs[(w * 31 + i) % 512], warm)
                for w in range(concurrency)
            ])
            lat: list = []
            t0 = time.perf_counter()
            await asyncio.gather(*[
                worker(
                    per_worker,
                    lambda i, w=w: reqs[(w * per_worker + i) % 512],
                    lat,
                )
                for w in range(concurrency)
            ])
            wall = time.perf_counter() - t0
            floor: list = []
            await asyncio.gather(*[
                worker(50, lambda i: floor_req, floor)
                for w in range(min(concurrency, 16))
            ])
            await channel.close()
            return lat, wall, floor

        lat, wall, floor = asyncio.new_event_loop().run_until_complete(
            drive()
        )
        # Scrape the batching telemetry BEFORE teardown: the server's
        # shutdown drain would otherwise skew the flush-reason mix.
        device_metrics = _scrape_device_metrics(_http_port)
        ok[0] = True
        lat_ms = np.asarray(lat) * 1e3
        floor_ms = np.asarray(floor) * 1e3
        rps = len(lat) / wall
        return (
            rps,
            float(np.percentile(lat_ms, 50)),
            float(np.percentile(lat_ms, 99)),
            float(np.percentile(floor_ms, 50)),
            device_metrics,
        )


def bench_fleet(n_replicas: int = 3):
    """Horizontal serving topology (the reference's N-limitadors-one-Redis
    deployment, doc/topologies.md): N replica processes share ONE gRPC
    port via SO_REUSEPORT, each deciding from its local write-behind view,
    all flushing to one shared authority over the network-authority
    protocol (a memory authority here so the bench isolates the serving
    plane; production points --authority-url at a TPU-table server).
    Reported: closed-loop aggregate throughput with 1 replica vs N — the
    scale-out that lifts the per-process Python gRPC ceiling."""
    import os
    import subprocess

    limits_path = _write_limits_file()
    rls_port = _free_port()
    auth_port, auth_http = _free_port(), _free_port()
    procs = []

    stderr_paths = []
    success = False

    def spawn(argv):
        stderr_path = _stderr_log_path()
        stderr_paths.append(stderr_path)
        proc = _spawn_server(argv, stderr_path)
        procs.append(proc)
        return proc, stderr_path

    # One Python client process tops out near the server's per-process
    # rate, so the load comes from several CLIENT processes; each reports
    # its own JSON line on stdout and the parent aggregates.
    _CLIENT = r"""
import asyncio, json, sys, time
import numpy as np
import grpc
sys.path.insert(0, {repo!r})
from limitador_tpu.server.proto import rls_pb2

PORT, CHANNELS, CONCURRENCY, PER_WORKER, SEED = (
    int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3]),
    int(sys.argv[4]), int(sys.argv[5]),
)

async def main():
    chans = [
        grpc.aio.insecure_channel(
            f"127.0.0.1:{{PORT}}", options=[("bench.chan", SEED * 100 + i)]
        )
        for i in range(CHANNELS)
    ]
    methods = [
        ch.unary_unary(
            "/envoy.service.ratelimit.v3.RateLimitService/ShouldRateLimit",
            request_serializer=lambda m: m.SerializeToString(),
            response_deserializer=rls_pb2.RateLimitResponse.FromString,
        )
        for ch in chans
    ]
    def make_req(user):
        req = rls_pb2.RateLimitRequest(domain="api")
        d = req.descriptors.add()
        e = d.entries.add(); e.key = "u"; e.value = user
        return req
    reqs = [make_req(f"user-{{i}}") for i in range(256)]
    async def worker(w, n, out):
        method = methods[w % CHANNELS]
        for i in range(n):
            t0 = time.perf_counter()
            await method(reqs[(SEED + w * n + i) % 256])
            out.append(time.perf_counter() - t0)
    warm = []
    await asyncio.gather(*[worker(w, 15, warm) for w in range(CONCURRENCY)])
    lat = []
    t0 = time.perf_counter()
    await asyncio.gather(*[
        worker(w, PER_WORKER, lat) for w in range(CONCURRENCY)
    ])
    wall = time.perf_counter() - t0
    for ch in chans:
        await ch.close()
    lat_ms = np.asarray(lat) * 1e3
    print(json.dumps({{
        "n": len(lat), "wall": wall,
        "p50": float(np.percentile(lat_ms, 50)),
        "p99": float(np.percentile(lat_ms, 99)),
    }}))

asyncio.run(main())
""".format(repo=os.path.dirname(os.path.abspath(__file__)))

    def drive(client_procs=4, concurrency=32, per_worker=120, channels=4):
        clients = [
            subprocess.Popen(
                [sys.executable, "-c", _CLIENT, str(rls_port),
                 str(channels), str(concurrency), str(per_worker), str(k)],
                stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                text=True,
            )
            for k in range(client_procs)
        ]
        results = []
        failures = []
        try:
            for proc in clients:
                out, _ = proc.communicate(timeout=300)
                if proc.returncode == 0 and out.strip():
                    results.append(json.loads(out.strip().splitlines()[-1]))
                else:
                    failures.append(proc.returncode)
        finally:
            for proc in clients:  # a timed-out reap must not leak clients
                if proc.poll() is None:
                    proc.kill()
        if failures:
            # A silently-dropped client would skew the aggregate without
            # any trace; refuse to report a partial number.
            raise RuntimeError(
                f"{len(failures)}/{len(clients)} fleet clients failed "
                f"(rcs {failures})"
            )
        total = sum(r["n"] for r in results)
        wall = max(r["wall"] for r in results)
        p50 = float(np.median([r["p50"] for r in results]))
        p99 = max(r["p99"] for r in results)
        return total / wall, p50, p99

    try:
        auth_proc, auth_err = spawn(
            [limits_path, "memory", "--rls-port", str(_free_port()),
             "--http-port", str(auth_http),
             "--authority-listen", f"127.0.0.1:{auth_port}"])
        _wait_http(auth_http, auth_proc, auth_err)

        def add_replica():
            http = _free_port()
            proc, err = spawn([limits_path, "cached",
                               "--rls-port", str(rls_port),
                               "--http-port", str(http),
                               "--authority-url", f"127.0.0.1:{auth_port}"])
            _wait_http(http, proc, err)

        add_replica()
        solo_rps, solo_p50, solo_p99 = drive()
        for _ in range(n_replicas - 1):
            add_replica()
        fleet_rps, fleet_p50, fleet_p99 = drive()
        scaling = fleet_rps / solo_rps if solo_rps else 0.0
        cores = os.cpu_count() or 1
        note = (
            "SO_REUSEPORT fan-in, one shared authority"
            if cores > n_replicas
            else f"topology validated; host has {cores} core(s), so "
            "replicas+clients contend and the ratio cannot show scale-out "
            "here — replicas are independent processes, so on one core per "
            "replica the aggregate scales with the replica count"
        )
        print(
            f"fleet: 1 replica {solo_rps/1e3:.1f}k req/s "
            f"(p50 {solo_p50:.2f}ms p99 {solo_p99:.2f}ms) -> "
            f"{n_replicas} replicas {fleet_rps/1e3:.1f}k req/s "
            f"(p50 {fleet_p50:.2f}ms p99 {fleet_p99:.2f}ms), "
            f"{scaling:.2f}x — {note}",
            file=sys.stderr,
        )
        emit(
            "fleet_should_rate_limit_per_sec",
            fleet_rps,
            "decisions/s",
            1e7,
            replicas=n_replicas,
            solo_rps=round(solo_rps, 1),
            scaling=round(scaling, 2),
            host_cores=cores,
            p50_ms=round(fleet_p50, 3),
            p99_ms=round(fleet_p99, 3),
        )
        success = True
    finally:
        for proc in procs:
            proc.terminate()
        for proc in procs:
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
        os.unlink(limits_path)
        if success:
            for path in stderr_paths:
                try:
                    os.unlink(path)
                except OSError:
                    pass
        else:
            print(
                f"server stderr kept at: {', '.join(stderr_paths)}",
                file=sys.stderr,
            )


def bench_grpc():
    """Closed-loop gRPC ShouldRateLimit over a real socket: p99 vs the 2ms
    BASELINE target (value = p99_ms, vs_baseline = 2.0 / p99 so >= 1.0
    beats the target)."""
    rps, p50, p99, floor_p50, device_metrics = grpc_closed_loop()
    print(
        f"grpc closed-loop: {rps/1e3:.1f}k req/s, p50 {p50:.2f}ms "
        f"p99 {p99:.2f}ms | no-storage floor p50 {floor_p50:.2f}ms "
        "(gRPC+loop overhead)",
        file=sys.stderr,
    )
    if device_metrics:
        print(
            "batching: queue-wait p99 "
            f"{device_metrics.get('queue_wait_p99_ms', float('nan'))}ms, "
            f"mean fill ratio {device_metrics.get('batch_fill_ratio', 0)}, "
            "deadline-flush share "
            f"{device_metrics.get('deadline_flush_share', 0)}",
            file=sys.stderr,
        )
    payload = {
        "metric": "grpc_should_rate_limit_p99_ms",
        "value": round(p99, 3),
        "unit": "ms",
        "vs_baseline": round(2.0 / p99, 4) if p99 > 0 else 0.0,
        "rps": round(rps, 1),
        "p50_ms": round(p50, 3),
        "floor_p50_ms": round(floor_p50, 3),
        **device_metrics,
    }
    irps, ip50, ip99, ifloor, _idev = grpc_closed_loop(native_ingress=True)
    print(
        f"native ingress closed-loop: {irps/1e3:.1f}k req/s, "
        f"p50 {ip50:.2f}ms p99 {ip99:.2f}ms | no-storage floor "
        f"p50 {ifloor:.2f}ms (vendored C++ HTTP/2 ingress)",
        file=sys.stderr,
    )
    payload.update({
        "ingress_rps": round(irps, 1),
        "ingress_p50_ms": round(ip50, 3),
        "ingress_p99_ms": round(ip99, 3),
        "ingress_floor_p50_ms": round(ifloor, 3),
    })
    print(json.dumps(payload))


def _run_matrix_config(config: str, timeout_s: float = 900.0, env=None):
    """Run one bench config in a subprocess and return its JSON line.
    Device-touching configs must run serially (a chip belongs to one
    process at a time); a config that times out, exits non-zero or
    prints no row fails the whole run."""
    import os
    import subprocess

    merged = dict(os.environ)
    if env:
        for k, v in env.items():
            if k == "XLA_FLAGS" and merged.get("XLA_FLAGS"):
                merged[k] = merged["XLA_FLAGS"] + " " + v
            else:
                merged[k] = v
    proc = subprocess.run(
        [sys.executable, __file__, "--config", config],
        capture_output=True, text=True, timeout=timeout_s, env=merged,
    )
    sys.stderr.write(proc.stderr)
    if proc.returncode == 0:
        for line in reversed(proc.stdout.strip().splitlines()):
            try:
                return json.loads(line)
            except (ValueError, TypeError):
                continue
    sys.exit(
        f"matrix config {config}: no JSON line (rc={proc.returncode})"
    )


def main():
    import os

    parser = argparse.ArgumentParser()
    parser.add_argument(
        "--config",
        default="device",
        choices=["device", "memory", "pipeline", "native", "lease",
                 "tenants", "sharded", "backends", "grpc", "fleet",
                 "pod", "flight", "tiered", "controller"],
    )
    # internal: one process of the pod sweep (spawned by bench_pod)
    parser.add_argument("--pod-worker-id", type=int, default=None,
                        help=argparse.SUPPRESS)
    parser.add_argument("--pod-worker-procs", type=int, default=1,
                        help=argparse.SUPPRESS)
    parser.add_argument("--pod-coordinator", default=None,
                        help=argparse.SUPPRESS)
    parser.add_argument("--pod-peer-ports", default=None,
                        help=argparse.SUPPRESS)
    parser.add_argument("--pod-native-ports", default=None,
                        help=argparse.SUPPRESS)
    parser.add_argument("--pod-out", default=None,
                        help=argparse.SUPPRESS)
    args = parser.parse_args()

    if args.config == "memory":
        return bench_memory()
    if args.config == "backends":
        return bench_backends()
    if args.config == "pipeline":
        return bench_pipeline()
    if args.config == "native":
        return bench_native()
    if args.config == "lease":
        return bench_lease()
    if args.config == "sharded":
        return bench_sharded()
    if args.config == "pod":
        if args.pod_worker_id is not None:
            return _bench_pod_worker(args)
        return bench_pod()
    if args.config == "grpc":
        return bench_grpc()
    if args.config == "fleet":
        return bench_fleet()
    if args.config == "flight":
        return bench_flight()
    if args.config == "tiered":
        return bench_tiered()
    if args.config == "controller":
        return bench_controller()

    # End-to-end gRPC latency evidence rides along with the headline
    # (device) run only. It runs FIRST — before this process initializes
    # jax — because the server subprocess needs the chip, and a chip
    # belongs to one process at a time. A server that finds no chip
    # exits (limitador_tpu/device.py), which fails the run here.
    extra = {}
    if args.config == "device":
        if os.environ.get("JAX_PLATFORMS") == "cpu":
            # the children would serve from the CPU by name and the
            # parent would refuse minutes later: refuse now
            print(
                "ERROR: JAX_PLATFORMS=cpu; the headline measures the "
                "chip and records no CPU number in its place",
                file=sys.stderr,
            )
            sys.exit(3)
        rps, p50, p99, floor_p50, device_metrics = grpc_closed_loop(
            concurrency=64, per_worker=120
        )
        print(
            f"grpc closed-loop: {rps/1e3:.1f}k req/s, p50 {p50:.2f}ms "
            f"p99 {p99:.2f}ms | no-storage floor p50 {floor_p50:.2f}ms "
            "(the floor is gRPC+loop overhead)",
            file=sys.stderr,
        )
        extra = {
            "grpc_rps": round(rps, 1),
            "grpc_p50_ms": round(p50, 3),
            "grpc_p99_ms": round(p99, 3),
            "grpc_floor_p50_ms": round(floor_p50, 3),
            **device_metrics,
        }
        rps, p50, p99, floor_p50, _idev = grpc_closed_loop(
            concurrency=64, per_worker=120, native_ingress=True
        )
        print(
            f"native ingress closed-loop: {rps/1e3:.1f}k req/s, "
            f"p50 {p50:.2f}ms p99 {p99:.2f}ms | no-storage floor "
            f"p50 {floor_p50:.2f}ms (vendored C++ HTTP/2 ingress)",
            file=sys.stderr,
        )
        extra.update({
            "ingress_rps": round(rps, 1),
            "ingress_p50_ms": round(p50, 3),
            "ingress_p99_ms": round(p99, 3),
            "ingress_floor_p50_ms": round(floor_p50, 3),
        })

    # Full matrix ride-along: the recorded artifact carries per-config
    # numbers — pipeline (with the queue-excluded datastore latency
    # histogram), native, and the sharded multi-chip model on the
    # eight-device virtual CPU mesh (CPU by declaration: the row's
    # sharded_platform says so) — not just the raw-kernel headline.
    # Subprocesses, run serially BEFORE this process takes the chip; a
    # row that fails, fails the run. BENCH_SKIP_MATRIX=1 skips for
    # quick runs.
    if (
        args.config == "device"
        and os.environ.get("BENCH_SKIP_MATRIX") != "1"
    ):
        matrix = [
            ("memory", None),
            ("pipeline", None), ("native", None),
            ("lease", None), ("tenants", None),
            ("sharded", {
                "JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
            }),
        ]
        for config, env in matrix:
            row = _run_matrix_config(config, env=env)
            extra[f"{config}_decisions_per_sec"] = row.get("value")
            for k in row:
                if k in (
                    "datastore_samples",
                    "native_serving_decisions_per_sec",
                    "native_serving_shards",
                    "native_serving_by_shards", "plan_cache_hit_ratio",
                    "pipeline_shards", "pipeline_plan_cache_hit_ratio",
                    "pipeline_mono_decisions_per_sec",
                ) or k.startswith(
                    ("datastore_p", "sharded_", "dispatch_chunk_",
                     "lease_")
                ):
                    extra[k] = row[k]

    require_chip()
    import jax

    from limitador_tpu.ops.kernel import (
        check_and_update_batch,
        make_table,
    )

    if args.config == "tenants":
        def device_step(n_keys, keys_batches, windows):
            state = make_table(n_keys)
            batch = keys_batches.shape[1]
            # Constant hit attributes stay device-resident (same rationale
            # as the headline bench: re-uploading them per batch is a
            # transfer tax, not part of the varying request stream).
            deltas = jax.device_put(np.ones(batch, np.int32))
            maxes = jax.device_put(np.full(batch, 1000, np.int32))
            req_ids = jax.device_put(np.arange(batch, dtype=np.int32))
            fresh = jax.device_put(np.zeros(batch, bool))
            bucket = jax.device_put(np.zeros(batch, bool))
            windows = jax.device_put(windows)
            jax.block_until_ready(
                (deltas, maxes, req_ids, fresh, bucket, windows))
            state, result = check_and_update_batch(
                state, keys_batches[0], deltas, maxes, windows, req_ids,
                fresh, bucket, np.int32(500))
            jax.block_until_ready(result.admitted)
            t0 = time.perf_counter()
            for i, keys in enumerate(keys_batches):
                state, result = check_and_update_batch(
                    state, keys, deltas, maxes, windows, req_ids, fresh,
                    bucket, np.int32(1000 + i))
            jax.block_until_ready(result.admitted)
            return keys_batches.shape[0] * batch / (time.perf_counter() - t0)

        return bench_tenants(device_step)

    n_keys = 1 << 20          # 1M distinct counters
    batch = 1 << 15           # 32768 requests per micro-batch
    n_batches = 64
    warmup = 4
    max_value = 1000
    window_ms = 60_000
    # BASELINE config 4: per-key TOKEN BUCKET over the zipf key stream —
    # capacity 1000 refilling at 1000/60s (GCRA interval 60ms/token), run
    # on the device kernel's bucket lane (ops/kernel.py). The fixed-window
    # variant rides along as an extra row for the r1-r3 trend.
    interval_ms = window_ms // max_value

    dev = jax.devices()[0]
    print(
        f"bench: {n_keys} keys zipf-0.99 per-key token-bucket (GCRA device "
        f"lane, I={interval_ms}ms), {n_batches}x{batch} decisions "
        f"on {dev.device_kind} ({dev.platform})",
        file=sys.stderr,
    )

    rng = np.random.default_rng(1234)
    state = make_table(n_keys)

    # Pre-generate the batches host-side (the serving plane builds these
    # arrays from descriptor keys; here the key->slot mapping is steady-state).
    keys = zipf_keys(n_keys, batch * n_batches, 0.99, rng).reshape(
        n_batches, batch
    )
    # The workload's hit attributes are constant across batches (uniform
    # limit, delta 1, one hit per request): keep them device-resident so
    # the measured stream is what actually varies — the key column plus
    # the result download. Re-uploading five constant arrays per batch
    # is a transfer tax, not part of the varying request stream.
    deltas = jax.device_put(np.ones(batch, np.int32))
    maxes = jax.device_put(np.full(batch, max_value, np.int32))
    windows = jax.device_put(np.full(batch, window_ms, np.int32))
    intervals = jax.device_put(np.full(batch, interval_ms, np.int32))
    req_ids = jax.device_put(np.arange(batch, dtype=np.int32))
    fresh = jax.device_put(np.zeros(batch, bool))
    bucket_on = jax.device_put(np.ones(batch, bool))
    bucket_off = jax.device_put(np.zeros(batch, bool))
    jax.block_until_ready(
        (deltas, maxes, windows, intervals, req_ids, fresh, bucket_on,
         bucket_off)
    )

    def step(state, slots, now_ms):
        # headline: per-key token bucket (config 4) on the device lane
        return check_and_update_batch(
            state, slots, deltas, maxes, intervals, req_ids, fresh,
            bucket_on, np.int32(now_ms),
        )

    def step_fw(state, slots, now_ms):
        return check_and_update_batch(
            state, slots, deltas, maxes, windows, req_ids, fresh,
            bucket_off, np.int32(now_ms),
        )

    # Warmup / compile
    for i in range(warmup):
        state, result = step(state, keys[i % n_batches], 1000 + i)
    jax.block_until_ready(result.admitted)

    # Throughput: pipelined dispatch, block at the end. Two measured
    # passes, best-of.
    rates = []
    for rep in range(2):
        t0 = time.perf_counter()
        for i in range(n_batches):
            state, result = step(state, keys[i], 2000 + rep * 100 + i)
        jax.block_until_ready(result.admitted)
        rates.append(n_batches * batch / (time.perf_counter() - t0))
    decisions_per_sec = max(rates)

    # Prefetch variant: explicitly device_put batch i+depth's key column
    # while batch i computes — double-buffered upload overlapping the
    # host->device link with compute where plain dispatch serializes
    # them. Both are legitimate serving dispatch disciplines; the
    # recorded headline takes the better, and both appear in the
    # artifact so the win (or absence of one) is visible per run.
    depth = 2
    prefetch_rates = []
    for rep in range(2):
        staged_q = [jax.device_put(keys[i]) for i in range(depth)]
        # Priming uploads settle BEFORE the clock starts, so the timed
        # window covers exactly the overlapped steady state (device_put
        # is async; unsynced priming would straddle t0 run-to-run).
        jax.block_until_ready(staged_q)
        t0 = time.perf_counter()
        for i in range(n_batches):
            if i + depth < n_batches:
                staged_q.append(jax.device_put(keys[i + depth]))
            state, result = step(state, staged_q[i], 3000 + rep * 100 + i)
        jax.block_until_ready(result.admitted)
        prefetch_rates.append(
            n_batches * batch / (time.perf_counter() - t0)
        )
    prefetch_rate = max(prefetch_rates)
    print(
        f"prefetch dispatch (double-buffered upload): "
        f"{prefetch_rate/1e6:.2f}M decisions/s vs {decisions_per_sec/1e6:.2f}M plain",
        file=sys.stderr,
    )
    extra["device_plain_decisions_per_sec"] = round(decisions_per_sec, 1)
    extra["device_prefetch_decisions_per_sec"] = round(prefetch_rate, 1)
    decisions_per_sec = max(decisions_per_sec, prefetch_rate)

    # Kernel-only ceiling: stage the key batches on device too, leaving
    # dispatch + compute + result download as the measured path.
    # Best-of-two like the throughput pass; the blocking latency phase
    # goes last.
    staged = [jax.device_put(keys[i]) for i in range(min(n_batches, 32))]
    jax.block_until_ready(staged)
    kernel_rate = 0.0
    for rep in range(2):
        t0 = time.perf_counter()
        for i, staged_keys in enumerate(staged):
            state, result = step(state, staged_keys, 4000 + rep * 100 + i)
        jax.block_until_ready(result.admitted)
        kernel_rate = max(
            kernel_rate, len(staged) * batch / (time.perf_counter() - t0)
        )
    print(
        f"kernel-only (keys pre-staged): {kernel_rate/1e6:.2f}M "
        "decisions/s",
        file=sys.stderr,
    )

    # Latency: per-batch round-trip (admission visible to the host), blocking.
    lat = []
    for i in range(min(n_batches, 32)):
        t0 = time.perf_counter()
        state, result = step(state, keys[i], 5000 + i)
        np.asarray(result.admitted)
        lat.append(time.perf_counter() - t0)
    lat_ms = np.array(lat) * 1e3
    print(
        f"throughput: {decisions_per_sec/1e6:.2f}M decisions/s | "
        f"blocking batch round-trip p50 {np.percentile(lat_ms, 50):.2f}ms "
        f"p99 {np.percentile(lat_ms, 99):.2f}ms "
        "(a launch has a round trip; pipelined dispatch hides it, see "
        "throughput)",
        file=sys.stderr,
    )

    extra["device_kernel_decisions_per_sec"] = round(kernel_rate, 1)

    # Fixed-window ride-along (same key stream, window cells) for the
    # r1-r3 headline trend; separate table so policies don't share slots.
    fw_state = make_table(n_keys)
    for i in range(2):
        fw_state, fw_res = step_fw(fw_state, keys[i], 1000 + i)
    jax.block_until_ready(fw_res.admitted)
    fw_rate = 0.0
    for rep in range(2):
        t0 = time.perf_counter()
        for i in range(n_batches):
            fw_state, fw_res = step_fw(fw_state, keys[i], 6000 + rep * 100 + i)
        jax.block_until_ready(fw_res.admitted)
        fw_rate = max(
            fw_rate, n_batches * batch / (time.perf_counter() - t0)
        )
    print(
        f"fixed-window ride-along: {fw_rate/1e6:.2f}M decisions/s",
        file=sys.stderr,
    )
    extra["device_fixed_window_decisions_per_sec"] = round(fw_rate, 1)
    extra["headline_policy"] = "token_bucket"

    emit(
        "should_rate_limit_decisions_per_sec",
        decisions_per_sec,
        "decisions/s",
        1e7,
        platform=dev.platform,
        **extra,
    )


if __name__ == "__main__":
    main()
