#!/usr/bin/env python3
"""Chip smoke: the served path, once, on the chip, checked against the oracle.

    python chip_smoke.py            # exit 0 only on a TPU, every check held

Leg one starts the real server the way a user would

    python -m limitador_tpu.server <limits> tpu --pipeline native --native-ingress

(every other flag at its default: a 2^20-slot device table, the 1 s usage
drain) and drives it over gRPC ``ShouldRateLimit`` against the C++ ingress
port: every distinct descriptor key once (miss lane, slot allocation, the
``fresh`` path), then Zipf-0.99 repeats over them (plan cache, C hot lane),
many requests in flight, over one fixed-window and one token-bucket
namespace so both lanes of the kernel compile and run. The same sequence
goes through ``RateLimiter(InMemoryStorage())``; the answers must agree per
key. Then the server's own account is read (platform, native libraries,
hot-lane hits, device-table occupancy, device batches, the top-k drain, a
counter read-back, a hot reload that clears half the table), and a second
boot shows what the compile cache saved. When the first server reports four
or more devices, leg two serves the same traffic from ``sharded`` storage
over all of them, plus a global (psum) namespace.

This script is a launcher and a client: the chip belongs to the server it
starts, so it never imports jax. Last stdout line on success:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

import argparse
import asyncio
import json
import math
import os
import re
import shutil
import socket
import subprocess
import sys
import tempfile
import time
import urllib.request

import grpc
import numpy as np

from limitador_tpu import Context, RateLimiter
from limitador_tpu.device import compile_cache_dir
from limitador_tpu.server.limits_file import load_limits_file
from limitador_tpu.server.proto import rls_pb2
from limitador_tpu.storage.in_memory import InMemoryStorage

CHECKOUT = os.path.dirname(os.path.abspath(__file__))
METHOD = "/envoy.service.ratelimit.v3.RateLimitService/ShouldRateLimit"
OK, OVER_LIMIT = 1, 2
CHANNELS = 4
#: first-touch compiles happen on the serving path, so the first request
#: at each batch-size bucket can take seconds on a cold cache: every
#: request's deadline, and a boot's, survive that
DEADLINE_S = 300.0
BOOT_TIMEOUT_S = 300.0

MAX_VALUE = 5
SECONDS = 3600  # long enough that no window rolls during a cold run
GLOBAL_MAX = 50
LIMITS_YAML = f"""\
- namespace: fw
  max_value: {MAX_VALUE}
  seconds: {SECONDS}
  conditions: []
  variables: ["descriptors[0].u"]
- namespace: tb
  max_value: {MAX_VALUE}
  seconds: {SECONDS}
  policy: token_bucket
  conditions: []
  variables: ["descriptors[0].u"]
"""
#: the token bucket's emission interval, an integer number of ms (what
#: makes it device-eligible, tpu/native_pipeline.py)
TB_INTERVAL_S = SECONDS / MAX_VALUE
GLOBAL_YAML = f"""\
- namespace: gns
  max_value: {GLOBAL_MAX}
  seconds: {SECONDS}
  conditions: []
  variables: []
"""

# jax's own log handler writes "LEVEL:time:logger:line: message"; the
# server's root handler repeats the record in another format, so the
# patterns pin jax's to count each program once.
_COMPILED = re.compile(
    r"jax\._src\.dispatch:\d+: Finished XLA compilation of (\S+) in "
    r"([0-9.]+) sec")
_CACHE_HIT = re.compile(
    r"jax\._src\.compiler:\d+: Persistent compilation cache hit")


def say(msg: str) -> None:
    print(msg, flush=True)


class Checks:
    """Every check of the run, in order; one that fails fails the run."""

    def __init__(self):
        self.leg = ""
        self.failed = []

    def check(self, ok: bool, what: str) -> None:
        say(f"  [{'ok' if ok else 'FAIL'}] {what}")
        if not ok:
            self.failed.append(f"{self.leg}: {what}")


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def cache_files() -> set:
    try:
        return set(os.listdir(compile_cache_dir()))
    except FileNotFoundError:
        return set()


class Server:
    """One ``python -m limitador_tpu.server`` child, stderr kept in a
    file. ``stop`` waits for the process to exit: the chip is free for
    the next boot only once this one is gone."""

    def __init__(self, name, limits_path, storage_args, workdir):
        self.name = name
        self.rls_port, self.http_port = free_port(), free_port()
        self.stderr_path = os.path.join(workdir, f"{name}.stderr.log")
        env = dict(os.environ)
        env["PYTHONPATH"] = CHECKOUT + os.pathsep + env.get("PYTHONPATH", "")
        # jax's own switch: one log line per program compiled (or read
        # from the cache) with its wall time, which is all the smoke
        # knows about compile cost. Changes logging only.
        env["JAX_LOG_COMPILES"] = "1"
        argv = [
            sys.executable, "-m", "limitador_tpu.server", limits_path,
            *storage_args,
            "--rls-port", str(self.rls_port),
            "--http-port", str(self.http_port),
        ]
        say(f"{name}: {' '.join(argv[1:])}")
        self.spawned = time.monotonic()
        with open(self.stderr_path, "w") as err:
            self.proc = subprocess.Popen(
                argv, cwd=CHECKOUT, env=env,
                stdout=subprocess.DEVNULL, stderr=err,
            )

    def wait_ready(self) -> float:
        """Seconds from spawn until GET /status answers."""
        while True:
            if self.proc.poll() is not None:
                raise RuntimeError(
                    f"{self.name} exited rc={self.proc.returncode} before "
                    f"serving; stderr ends:\n{self.stderr()[-2000:]}")
            try:
                self.get("/status", timeout=1)
                return time.monotonic() - self.spawned
            except OSError:
                pass
            if time.monotonic() - self.spawned > BOOT_TIMEOUT_S:
                raise RuntimeError(
                    f"{self.name} not serving after {BOOT_TIMEOUT_S:.0f}s")
            time.sleep(0.1)

    def get(self, path: str, timeout: float = 120):
        with urllib.request.urlopen(
            f"http://127.0.0.1:{self.http_port}{path}", timeout=timeout
        ) as resp:
            return json.loads(resp.read())

    def stderr(self) -> str:
        with open(self.stderr_path, errors="replace") as f:
            return f.read()

    def compiles(self):
        """([(program, seconds)] this boot compiled or read from the
        compile cache, how many of them were cache reads), from its
        JAX_LOG_COMPILES lines."""
        log = self.stderr()
        return ([(m.group(1), float(m.group(2)))
                 for m in _COMPILED.finditer(log)],
                len(_CACHE_HIT.findall(log)))

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()


def request_blob(namespace: str, user: str) -> bytes:
    req = rls_pb2.RateLimitRequest(domain=namespace)
    entry = req.descriptors.add().entries.add()
    entry.key, entry.value = "u", user
    return req.SerializeToString()


def zipf_ranks(n_keys: int, n_samples: int, s: float, rng) -> np.ndarray:
    """Bounded Zipf(s) over [0, n_keys) by inverse CDF over rank weights."""
    cdf = np.cumsum(1.0 / np.arange(1, n_keys + 1, dtype=np.float64) ** s)
    return np.searchsorted(cdf, rng.random(n_samples) * cdf[-1])


def drive(port: int, blobs, order, inflight: int):
    """Send ``blobs[order[i]]`` for every i with ``inflight`` requests
    outstanding over ``CHANNELS`` connections. Returns (codes, seconds,
    errors): the answer code per request (-1 = the call failed), its
    latency, and a sample of distinct failure texts."""
    codes = np.full(len(order), -1, np.int8)
    lat = np.zeros(len(order), np.float64)
    errors = {}

    async def run():
        chans = [
            grpc.aio.insecure_channel(
                f"127.0.0.1:{port}", options=[("smoke.chan", i)])
            for i in range(CHANNELS)
        ]
        calls = [
            ch.unary_unary(
                METHOD, request_serializer=None,
                response_deserializer=rls_pb2.RateLimitResponse.FromString)
            for ch in chans
        ]
        cursor = iter(range(len(order)))

        async def worker(call):
            for i in cursor:
                t0 = time.perf_counter()
                try:
                    resp = await call(blobs[order[i]], timeout=DEADLINE_S)
                    codes[i] = resp.overall_code
                except grpc.aio.AioRpcError as exc:
                    text = f"{exc.code().name}: {exc.details()}"
                    errors[text] = errors.get(text, 0) + 1
                lat[i] = time.perf_counter() - t0

        await asyncio.gather(*[
            worker(calls[w % CHANNELS])
            for w in range(min(inflight, len(order)))
        ])
        for ch in chans:
            await ch.close()

    asyncio.run(run())
    return codes, lat, errors


def oracle_admitted(limits_path: str, namespaces, users, order) -> np.ndarray:
    """The plain reference: the same sequence through the in-memory
    oracle (sized like the device table, not upstream's 10k-counter
    default), OK answers counted per key."""
    limiter = RateLimiter(InMemoryStorage(1 << 20))
    for limit in load_limits_file(limits_path):
        limiter.add_limit(limit)
    ctxs = []
    for user in users:
        ctx = Context()
        ctx.list_binding("descriptors", [{"u": user}])
        ctxs.append(ctx)
    admitted = np.zeros(len(users), np.int64)
    for k in order:
        if not limiter.check_rate_limited_and_update(
            namespaces[k], ctxs[k], 1
        ).limited:
            admitted[k] += 1
    return admitted


def check_answers(checks, order, codes, errors, namespaces, users,
                  limits_path, elapsed_s: float) -> np.ndarray:
    """Zero failures, and per-key parity with the oracle in both lanes.
    Returns the OK answers counted per key."""
    n_keys = len(users)
    failed = int((codes == -1).sum())
    unknown = int((codes == 0).sum())
    checks.check(
        failed == 0 and unknown == 0,
        f"{len(order)} requests answered: {failed} failed, {unknown} UNKNOWN"
        + (f" ({dict(list(errors.items())[:3])})" if errors else ""))
    got = np.bincount(order[codes == OK], minlength=n_keys)
    want = oracle_admitted(limits_path, namespaces, users, order)
    sent = np.bincount(order, minlength=n_keys)
    is_fw = np.asarray([ns == "fw" for ns in namespaces])
    is_tb = np.asarray([ns == "tb" for ns in namespaces])
    wrong = np.flatnonzero(is_fw & (got != want))
    checks.check(
        wrong.size == 0,
        f"oracle parity, fixed window: {int(is_fw.sum()) - wrong.size}/"
        f"{int(is_fw.sum())} keys admitted exactly the oracle's count"
        + (f"; first off: key {wrong[0]} got {got[wrong[0]]} want "
           f"{want[wrong[0]]}" if wrong.size else ""))
    # A bucket refills with wall time, which the oracle's run did not
    # share: at least the oracle's count (= min(n, max), it ran in
    # seconds), at most that plus what the elapsed time can have refilled.
    refill = math.ceil(elapsed_s / TB_INTERVAL_S)
    upper = np.minimum(sent, want + refill)
    wrong = np.flatnonzero(is_tb & ((got < want) | (got > upper)))
    checks.check(
        wrong.size == 0,
        f"oracle parity, token bucket: {int(is_tb.sum()) - wrong.size}/"
        f"{int(is_tb.sum())} keys within [oracle, oracle + {refill} "
        f"refilled in {elapsed_s:.0f}s]"
        + (f"; first off: key {wrong[0]} got {got[wrong[0]]} want "
           f"{want[wrong[0]]}..{upper[wrong[0]]}" if wrong.size else ""))
    return got


def check_device(checks, stats, want_count: int) -> dict:
    device = stats.get("device") or {}
    platform = device.get("platform")
    checks.check(
        platform == "tpu",
        f"server runs on platform {platform or 'unknown'} (must be tpu)")
    checks.check(
        bool(device.get("kind")) and int(device.get("count") or 0) >= want_count,
        f"device kind {device.get('kind')!r}, count {device.get('count')}")
    return device


def drive_traffic(server, keys, repeats, seed, inflight, extra=()):
    """The smoke's traffic against one server: key 0 alone (the
    time-to-first-answer), every other distinct key once, then Zipf-0.99
    repeats. Returns what check_answers needs plus the timings."""
    rng = np.random.default_rng(seed)
    namespaces = ["fw" if k % 2 == 0 else "tb" for k in range(keys)]
    users = [f"user-{k}" for k in range(keys)]
    blobs = [request_blob(ns, u) for ns, u in zip(namespaces, users)]
    blobs.extend(extra)
    first = np.arange(keys)
    again = zipf_ranks(keys, repeats, 0.99, rng)

    started = time.monotonic()
    codes0, lat0, err0 = drive(server.rls_port, blobs, first[:1], 1)
    ttfa = time.monotonic() - server.spawned
    say(f"  first answer {ttfa:.2f}s after spawn "
        f"(the request itself took {lat0[0]:.2f}s)")
    t0 = time.monotonic()
    codes1, lat1, err1 = drive(server.rls_port, blobs, first[1:], inflight)
    t1 = time.monotonic()
    say(f"  {keys} distinct keys first seen in {t1 - t0:.1f}s")
    codes2, lat2, err2 = drive(server.rls_port, blobs, again, inflight)
    t2 = time.monotonic()
    say(f"  {repeats} Zipf-0.99 repeats in {t2 - t1:.1f}s")
    lat = np.concatenate([lat0, lat1, lat2])
    cold = lat[lat > 1.0]
    say(f"  {cold.size} requests waited over 1s (first-touch compiles on "
        f"the serving path), the longest {lat.max():.2f}s")
    return {
        "order": np.concatenate([first, again]),
        "codes": np.concatenate([codes0, codes1, codes2]),
        "errors": {**err0, **err1, **err2},
        "namespaces": namespaces, "users": users, "blobs": blobs,
        "elapsed_s": t2 - started, "ttfa_s": ttfa,
    }


def report_compiles(server, label: str):
    compiles, cache_hits = server.compiles()
    if compiles:
        name, secs = max(compiles, key=lambda c: c[1])
        say(f"  {label}: {len(compiles)} programs, {cache_hits} of them "
            f"read from the compile cache, {sum(s for _n, s in compiles):.1f}s "
            f"in all, the slowest {name} {secs:.2f}s")
    return compiles


def leg_one(checks, args, workdir) -> dict:
    checks.leg = "leg one"
    say("== leg one: tpu storage, native pipeline, C++ ingress ==")
    limits_path = os.path.join(workdir, "limits.yaml")
    with open(limits_path, "w") as f:
        f.write(LIMITS_YAML)
    storage_args = ["tpu", "--pipeline", "native", "--native-ingress"]
    cache_before = cache_files()
    server = Server("boot-1", limits_path, storage_args, workdir)
    try:
        ready = server.wait_ready()
        say(f"  serving {ready:.2f}s after spawn")
        t = drive_traffic(server, args.keys, args.repeats, args.seed,
                          args.inflight)
        got = check_answers(
            checks, t["order"], t["codes"], t["errors"], t["namespaces"],
            t["users"], limits_path, t["elapsed_s"])

        say("the server's own account:")
        stats = server.get("/debug/stats")
        device = check_device(checks, stats, 1)
        build = stats.get("native_build") or {}
        for lib in ("hostpath", "h2ingress"):
            state = build.get(lib) or {}
            checks.check(
                bool(state.get("loaded")),
                f"native library {lib} loaded"
                + (f" (built in {state['build_seconds']}s)"
                   if state.get("build_seconds") else "")
                + (f": {state.get('build_error')}"
                   if state.get("build_error") else ""))
        checks.check(
            "native HTTP/2 ingress on" in server.stderr(),
            "boot banner says the native HTTP/2 ingress is on")
        lane = stats.get("native_hot_lane") or {}
        checks.check(
            int(lane.get("hits") or 0) > 0,
            f"native_hot_lane.hits {lane.get('hits')} "
            f"(misses {lane.get('misses')})")
        occupied = sum(s["occupied"] for s in stats.get("shards") or [])
        capacity = sum(s["capacity"] for s in stats.get("shards") or [])
        checks.check(
            occupied == args.keys,
            f"device table occupied {occupied} of {capacity} slots "
            f"({args.keys} distinct keys sent)")
        # the ingress pump launches through the hot lane, which counts
        # its own finished launches; the batchers count flushes
        launches = int(((stats.get("native_telemetry") or {})
                        .get("hot_finish") or {}).get("count") or 0)
        checks.check(
            launches > 0 and int(lane.get("staged_hits") or 0) > 0,
            f"{launches} device batches finished by the hot lane, "
            f"{lane.get('staged_hits')} hits staged into them")

        # the lax.top_k drain over the whole table, every second under
        # the storage lock: let two periods pass, then time GET
        # /debug/top, which drains once more before it answers
        time.sleep(2.5)
        top = server.get("/debug/top")
        checks.check(
            bool(top.get("top")),
            f"/debug/top lists {len(top.get('top') or [])} counters after "
            f"{server.get('/debug/stats')['tenant_usage']['drains']} drains")
        drain_ms = []
        for _ in range(9):
            t0 = time.perf_counter()
            server.get("/debug/top")
            drain_ms.append((time.perf_counter() - t0) * 1e3)
        say(f"  GET /debug/top (one top_k drain + attribution + HTTP): "
            f"median {float(np.median(drain_ms)):.1f}ms of 9")

        # read_slots: every fixed-window counter read back from the device
        t0 = time.perf_counter()
        counters = server.get("/counters/fw", timeout=300)
        n_fw = sum(ns == "fw" for ns in t["namespaces"])
        remaining = {
            c["set_variables"]["descriptors[0].u"]: c["remaining"]
            for c in counters
        }
        off = [
            u for k, u in enumerate(t["users"])
            if t["namespaces"][k] == "fw"
            and remaining.get(u) != MAX_VALUE - got[k]
        ]
        checks.check(
            len(counters) == n_fw and not off,
            f"GET /counters/fw read {len(counters)} counters back in "
            f"{time.perf_counter() - t0:.1f}s, {n_fw - len(off)}/{n_fw} "
            "with the remaining the answers imply")

        # clear_slots: a hot reload that drops the token-bucket namespace
        # frees its half of the table
        version = server.get("/status")["limits_file_version"]
        with open(limits_path, "w") as f:
            f.write(LIMITS_YAML.split("- namespace: tb")[0])
        waited = time.monotonic()
        while (server.get("/status")["limits_file_version"] == version
               and time.monotonic() - waited < 30):
            time.sleep(0.2)
        stats = server.get("/debug/stats")
        occupied = sum(s["occupied"] for s in stats["shards"])
        checks.check(
            occupied == n_fw,
            f"after reloading limits without namespace tb the device table "
            f"holds {occupied} slots ({n_fw} fixed-window keys)")
        with open(limits_path, "w") as f:
            f.write(LIMITS_YAML)
    finally:
        server.stop()
    say(f"  boot-1 exited rc={server.proc.returncode}")
    cold = report_compiles(server, "boot-1")
    checks.check(bool(cold), "boot-1 logged the programs it compiled")
    for name in ("drain_top_hits", "check_and_update"):
        took = [s for n, s in cold if name in n]
        if took:
            say(f"  {name}: {len(took)} programs, the slowest "
                f"{max(took):.2f}s")
    cache_mid = cache_files()
    say(f"  compile cache {compile_cache_dir()}: {len(cache_before)} files "
        f"before boot-1, {len(cache_mid)} after")

    say("second boot, same command:")
    server2 = Server("boot-2", limits_path, storage_args, workdir)
    try:
        ready2 = server2.wait_ready()
        wave = np.arange(min(256, args.keys))
        codes0, _lat, _e = drive(server2.rls_port, t["blobs"], wave[:1], 1)
        ttfa2 = time.monotonic() - server2.spawned
        codes, _lat, errors = drive(server2.rls_port, t["blobs"], wave[1:], 1)
        codes = np.concatenate([codes0, codes])
        checks.check(
            bool((codes == OK).all()),
            f"boot-2 answered a serial wave of {wave.size} first-seen keys, "
            f"all OK" + (f" ({errors})" if errors else ""))
    finally:
        server2.stop()
    report_compiles(server2, "boot-2")
    added = cache_files() - cache_mid
    say(f"  serving after {ready:.2f}s cold, {ready2:.2f}s warm; first "
        f"answer after {t['ttfa_s']:.2f}s cold, {ttfa2:.2f}s warm")
    checks.check(
        not added,
        f"boot-2 added {len(added)} files to the compile cache")
    checks.check(
        bool(cache_mid), f"the compile cache holds {len(cache_mid)} files")
    return device


def leg_two(checks, args, workdir) -> None:
    checks.leg = "leg two"
    say("== leg two: sharded storage over every device, compiled pipeline, "
        "one global (psum) namespace ==")
    limits_path = os.path.join(workdir, "limits-sharded.yaml")
    with open(limits_path, "w") as f:
        f.write(LIMITS_YAML + GLOBAL_YAML)
    server = Server(
        "sharded", limits_path,
        ["sharded", "--pipeline", "compiled", "--global-namespaces", "gns"],
        workdir)
    try:
        ready = server.wait_ready()
        say(f"  serving {ready:.2f}s after spawn")
        n_global = 4 * GLOBAL_MAX
        extra = [request_blob("gns", f"g-{i}") for i in range(n_global)]
        t = drive_traffic(server, args.keys, args.repeats, args.seed,
                          args.inflight, extra=extra)
        check_answers(
            checks, t["order"], t["codes"], t["errors"], t["namespaces"],
            t["users"], limits_path, t["elapsed_s"])
        # One at a time: global hits are staged round-robin over the
        # shards, so serial requests leave a partial on every shard and
        # each admission reads their psum. (Hits that share a batch
        # carry the documented one-batch-per-remote-shard bound, which
        # is not what this leg checks.)
        gorder = np.arange(args.keys, args.keys + n_global)
        gcodes, _lat, gerrors = drive(server.rls_port, t["blobs"], gorder, 1)
        admitted = int((gcodes == OK).sum())
        checks.check(
            admitted == GLOBAL_MAX and bool((gcodes[:GLOBAL_MAX] == OK).all())
            and bool((gcodes[GLOBAL_MAX:] == OVER_LIMIT).all()),
            f"global namespace: {admitted} of {n_global} serial requests "
            f"admitted, the limit is {GLOBAL_MAX}"
            + (f" ({gerrors})" if gerrors else ""))

        say("the server's own account:")
        stats = server.get("/debug/stats")
        device = check_device(checks, stats, 4)
        checks.check(
            f"count {device.get('count')}" in server.stderr(),
            "boot log names the devices")
        local = [s for s in stats["shards"] if s["shard"] != "global"]
        occupied = [s["occupied"] for s in local]
        checks.check(
            len(local) == device.get("count") and min(occupied) > 0
            and max(occupied) < args.keys and sum(occupied) == args.keys,
            f"{len(local)} shards hold {occupied} counters, "
            f"{sum(occupied)} of {args.keys} keys in all")
        glob = [s for s in stats["shards"] if s["shard"] == "global"]
        checks.check(
            bool(glob) and glob[0]["occupied"] == 1,
            f"the global region holds {glob[0]['occupied'] if glob else 0} "
            "counter")
    finally:
        server.stop()
    report_compiles(server, "sharded")


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=20)
    parser.add_argument("--keys", type=int, default=100_000,
                        help="distinct descriptor keys (BASELINE config 2)")
    parser.add_argument("--repeats", type=int, default=200_000,
                        help="Zipf-0.99 repeats over them (config 4's skew)")
    parser.add_argument("--inflight", type=int, default=1024,
                        help="requests outstanding at once")
    args = parser.parse_args()

    checks = Checks()
    workdir = tempfile.mkdtemp(prefix="chip-smoke-")
    say(f"server logs under {workdir} (kept when a check fails)")
    device = leg_one(checks, args, workdir)
    if int(device.get("count") or 0) >= 4:
        leg_two(checks, args, workdir)
    else:
        say(f"leg two (sharded storage over four chips) NOT RUN: the first "
            f"server reported {device.get('count')} device(s)")

    assert "jax" not in sys.modules, "the smoke must leave the chip alone"
    if checks.failed:
        print(f"chip_smoke FAILED ({len(checks.failed)} checks); server "
              f"stderr kept under {workdir}", file=sys.stderr)
        for what in checks.failed:
            print(f"  FAIL: {what}", file=sys.stderr)
        return 1
    shutil.rmtree(workdir)
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"],
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
