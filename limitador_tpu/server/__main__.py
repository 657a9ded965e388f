"""limitador-tpu server binary.

CLI/env layering mirrors /root/reference/limitador-server/src/main.rs
(clap subcommands per storage, main.rs:483-730) and config.rs's env
registry; env vars keep the reference's names (LIMITS_FILE,
ENVOY_RLS_HOST/PORT, HTTP_API_HOST/PORT, RATE_LIMIT_HEADERS,
LIMIT_NAME_IN_PROMETHEUS_LABELS). CLI wins over env, env over defaults
(doc/server/configuration.md:46).

    python -m limitador_tpu.server LIMITS_FILE [storage] [options]

Storages: tpu (default — device-resident counters), memory, disk,
distributed. ``--validate`` parses the limits file and exits.
"""

from __future__ import annotations

import argparse
import asyncio
import inspect
import json
import logging
import os
import signal
import sys

from ..core.cel import CelError
from ..core.limiter import AsyncRateLimiter, RateLimiter
from ..observability.metrics import PrometheusMetrics
from .http_api import run_http_server
from .limits_file import LimitsFileError, LimitsFileWatcher, load_limits_file
from .rls import (
    RATE_LIMIT_HEADERS_DRAFT03,
    RATE_LIMIT_HEADERS_NONE,
    serve_rls,
)

__all__ = ["main", "build_parser"]

log = logging.getLogger("limitador")


class _JsonFormatter(logging.Formatter):
    """Structured JSON log lines, shaped like the reference's
    tracing_subscriber json layer (main.rs:922-957): timestamp, level,
    target, fields.message."""

    def format(self, record: logging.LogRecord) -> str:
        entry = {
            "timestamp": self.formatTime(record, "%Y-%m-%dT%H:%M:%S%z"),
            "level": record.levelname,
            "target": record.name,
            "fields": {"message": record.getMessage()},
        }
        if record.exc_info:
            entry["fields"]["exception"] = self.formatException(
                record.exc_info
            )
        return json.dumps(entry)


_LEVELS = {
    "trace": logging.DEBUG,
    "debug": logging.DEBUG,
    "info": logging.INFO,
    "warn": logging.WARNING,
    "warning": logging.WARNING,
    "error": logging.ERROR,
}


def _setup_logging(structured: bool, level: str) -> None:
    handler = logging.StreamHandler(sys.stderr)
    if structured:
        handler.setFormatter(_JsonFormatter())
    else:
        handler.setFormatter(
            logging.Formatter("%(asctime)s %(levelname)s %(name)s: "
                              "%(message)s")
        )
    root = logging.getLogger()
    root.handlers[:] = [handler]
    root.setLevel(_LEVELS.get(level.lower(), logging.INFO))


def _env(name, default=None):
    return os.environ.get(name, default)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="limitador-tpu-server",
        description="TPU-native rate limiter (Envoy RLS v3 + HTTP API)",
    )
    p.add_argument(
        "limits_file",
        nargs="?",
        default=_env("LIMITS_FILE"),
        help="YAML limits file (env: LIMITS_FILE)",
    )
    p.add_argument(
        "storage",
        nargs="?",
        default=_env("STORAGE", "tpu"),
        choices=["tpu", "sharded", "memory", "disk", "distributed", "cached"],
        help="counter storage backend (default: tpu); 'cached' is the "
        "write-behind topology over a disk authority (--disk-path); "
        "'sharded' splits the counter table over every visible device "
        "(keys routed by hash, global namespaces psum-replicated)",
    )
    p.add_argument("--rls-host", default=_env("ENVOY_RLS_HOST", "0.0.0.0"))
    p.add_argument(
        "--rls-port", type=int, default=int(_env("ENVOY_RLS_PORT", "8081"))
    )
    p.add_argument("--http-host", default=_env("HTTP_API_HOST", "0.0.0.0"))
    p.add_argument(
        "--http-port", type=int, default=int(_env("HTTP_API_PORT", "8080"))
    )
    p.add_argument(
        "--limit-name-in-labels",
        action="store_true",
        default=_env("LIMIT_NAME_IN_PROMETHEUS_LABELS") == "1",
        help="add limit names to prometheus labels",
    )
    p.add_argument(
        "--tracing-endpoint",
        default=_env("TRACING_ENDPOINT"),
        help="OTLP endpoint for span export (uses opentelemetry-sdk when "
        "installed, else the vendored OTLP/HTTP+JSON pipeline)",
    )
    p.add_argument(
        "--metric-labels",
        default=_env("METRIC_LABELS"),
        help="CEL map literal evaluated per request for extra prometheus "
        "labels, e.g. \"{'tenant': descriptors[0].tenant}\"",
    )
    p.add_argument(
        "--metric-labels-file",
        default=_env("METRIC_LABELS_FILE"),
        help="file holding the CEL label map; watched and hot-reloaded "
        "(label NAMES are fixed at startup, value expressions may change)",
    )
    p.add_argument(
        "--grpc-reflection-service",
        action="store_true",
        help="enable gRPC server reflection (requires grpcio-reflection)",
    )
    p.add_argument(
        "--rate-limit-headers",
        choices=[RATE_LIMIT_HEADERS_NONE, RATE_LIMIT_HEADERS_DRAFT03],
        default=_env("RATE_LIMIT_HEADERS", RATE_LIMIT_HEADERS_NONE),
    )
    p.add_argument(
        "--validate",
        action="store_true",
        help="validate the limits file and exit",
    )
    p.add_argument(
        "--structured-logs",
        action="store_true",
        default=_env("STRUCTURED_LOGS", "") == "1",
        help="emit structured JSON log lines (main.rs:577-580)",
    )
    p.add_argument(
        "--log-level",
        default=_env("LIMITADOR_LOG", _env("RUST_LOG", "info")),
        help="log level: trace|debug|info|warn|error",
    )
    def _positive_interval(value: str) -> float:
        interval = float(value)
        if interval <= 0:
            raise argparse.ArgumentTypeError(
                "poll interval must be > 0 seconds"
            )
        return interval

    p.add_argument(
        "--limits-poll-interval", type=_positive_interval,
        default=_positive_interval(_env("LIMITS_FILE_POLL_INTERVAL", "1.0")),
        help="limits/labels file change-poll interval in seconds, > 0 "
        "(the reference watches via inotify, main.rs limits_file "
        "watcher; polling is filesystem-agnostic — ConfigMap symlink "
        "swaps included)",
    )
    # storage tuning
    p.add_argument(
        "--cache-size", type=int, default=None,
        help="qualified-counter cache cap (memory/tpu)",
    )
    p.add_argument(
        "--tpu-capacity", type=int,
        default=int(_env("TPU_TABLE_CAPACITY", str(1 << 20))),
        help="device counter-table capacity (tpu)",
    )
    p.add_argument(
        "--batch-delay-us", type=int,
        default=int(_env("TPU_BATCH_DELAY_US", "500")),
        help="micro-batcher linger in microseconds (tpu)",
    )

    def _dispatch_chunk(value: str):
        if value in ("auto", ""):
            return None  # auto-tuned from the queue-wait signal
        if value in ("off", "0"):
            return 0  # monolithic dispatch
        chunk = int(value)
        if chunk < 0:
            raise argparse.ArgumentTypeError(
                "dispatch chunk must be >= 0, 'off' or 'auto'"
            )
        return chunk

    p.add_argument(
        "--dispatch-chunk", type=_dispatch_chunk,
        default=_dispatch_chunk(_env("TPU_DISPATCH_CHUNK", "auto")),
        help="tpu: hits per pipelined sub-batch launch — a flush splits "
        "into overlapping chunks so a request's device round trip is its "
        "chunk's, not the whole batch's (docs/configuration.md). "
        "'auto' (default) sizes chunks from the device-plane queue-wait "
        "signal against the 2ms latency budget; 'off'/0 dispatches "
        "monolithically; N pins the chunk size",
    )
    p.add_argument(
        "--pipeline",
        choices=["standard", "compiled", "native"],
        default=_env("TPU_PIPELINE", "standard"),
        help="tpu request path: per-request CEL (standard), batch-compiled "
        "vectorized masks (compiled), or the C++ columnar host path for "
        "ShouldRateLimit (native; falls back to compiled when the native "
        "library is unavailable)",
    )
    p.add_argument(
        "--serving-shards", type=int,
        default=int(_env("SERVING_SHARDS", "1")),
        help="number of RLS gRPC serving loops: each extra shard is a "
        "thread with its own event loop and its own server on the SAME "
        "port (SO_REUSEPORT), all feeding the shared device lane — "
        "accept/parse/future-resolution parallelize across cores "
        "(requires a batched tpu storage to pay off; 1 = single loop)",
    )
    p.add_argument(
        "--plan-cache-size", type=int,
        default=int(_env("PLAN_CACHE_SIZE", str(1 << 16))),
        help="hot-descriptor decision-plan cache entries per pipeline "
        "(byte-identical repeat requests skip parse/CEL/slot hashing; "
        "epoch-invalidated on every limits change; 0 disables)",
    )
    p.add_argument(
        "--native-hot-lane",
        choices=["on", "off"],
        default=_env("TPU_NATIVE_HOT_LANE", "on"),
        help="zero-Python hot lane for the native pipeline: repeat "
        "descriptors run plan lookup, columnar staging and response "
        "build in one GIL-free C call (C-side mirror of the decision-"
        "plan cache; epoch/slot-coherent). 'off' pins the pure-Python "
        "cached lane — byte-identical decisions, host-bound throughput",
    )
    p.add_argument(
        "--lease-mode",
        choices=["on", "off"],
        default=_env("TPU_LEASE_MODE", "off"),
        help="quota-leasing edge tier (requires --pipeline native with "
        "the hot lane): hot descriptors get pre-debited token batches "
        "attached to their mirrored plans, so repeat decisions complete "
        "with zero device work; over-admission per counter is bounded "
        "by its outstanding leased tokens, grants never exceed the "
        "remaining window headroom, and cold/exact-path keys stay "
        "exact. 'off' (default) is byte-identical to the pre-lease "
        "serving path",
    )
    p.add_argument(
        "--lease-max-tokens", type=int,
        default=int(_env("TPU_LEASE_MAX_TOKENS", "1024")),
        help="per-lease token cap (the broker sizes each grant from "
        "observed demand up to this, doubling on renewal and halving "
        "on a headroom denial)",
    )
    p.add_argument(
        "--native-ingress",
        action="store_true",
        default=_env("TPU_NATIVE_INGRESS", "") == "1",
        help="serve ShouldRateLimit through the vendored C++ HTTP/2 "
        "ingress on --rls-port (requires tpu storage, --pipeline native, "
        "headers NONE); the Python gRPC server (Kuadrant + Envoy with "
        "headers) moves to --rls-port + 1",
    )
    # pod-scale serving (docs/configuration.md "Pod-scale serving"):
    # jax.distributed global mesh + shard-aware routed ingress
    p.add_argument(
        "--pod-coordinator", default=_env("TPU_POD_COORDINATOR"),
        help="pod: jax.distributed coordinator address (host:port); "
        "required when --pod-processes > 1. Process 0 must be reachable "
        "there before the others start",
    )
    p.add_argument(
        "--pod-processes", type=int,
        default=int(_env("TPU_POD_PROCESSES", "1")),
        help="pod: total number of pod processes (hosts); 1 = no pod "
        "(the default single-host topology)",
    )
    p.add_argument(
        "--pod-process-id", type=int,
        default=int(_env("TPU_POD_PROCESS_ID", "0")),
        help="pod: this process's id in [0, --pod-processes)",
    )
    p.add_argument(
        "--pod-peer", action="append", default=None,
        help="pod: peer-lane address of each pod process in process-id "
        "order, repeatable (env TPU_POD_PEERS, comma separated); a "
        "descriptor owned by another host is forwarded once over this "
        "lane",
    )
    p.add_argument(
        "--pod-peer-listen", default=_env("TPU_POD_PEER_LISTEN"),
        help="pod: bind address of this host's peer lane "
        "(default 0.0.0.0:<rls-port + 2>)",
    )
    # pod resilience plane (docs/configuration.md "Pod resilience"):
    # peer health + retry/hedge on the lane, degraded-owner failover
    # with journaled reconcile behind a per-peer breaker
    p.add_argument(
        "--pod-degraded-mode", choices=["on", "off"],
        default=_env("TPU_POD_DEGRADED_MODE", "on"),
        help="pod: on (default) = forward failures feed a per-peer "
        "breaker and fail over to a local exact stand-in that journals "
        "deltas for replay on recovery (plus one jittered retry for "
        "suspect peers); off = PR 10 behavior, a peer failure fails "
        "that request (UNAVAILABLE/500)",
    )
    p.add_argument(
        "--pod-hedge-ms", type=float,
        default=float(_env("TPU_POD_HEDGE_MS", "0")),
        help="pod: >0 enables hedged forwards — when an in-flight "
        "forward outlasts max(this floor, the tracked peer p99) a "
        "second attempt races it on a fresh channel; 0 (default) "
        "disables hedging",
    )
    p.add_argument(
        "--pod-peer-breaker-failures", type=int,
        default=int(_env("TPU_POD_PEER_BREAKER_FAILURES", "3")),
        help="pod: consecutive forward failures that open a peer's "
        "failover breaker",
    )
    p.add_argument(
        "--pod-peer-breaker-reset-ms", type=float,
        default=float(_env("TPU_POD_PEER_BREAKER_RESET_MS", "2000")),
        help="pod: ms an open peer breaker dwells before recovery "
        "probes may close it",
    )
    # pod observability plane (docs/observability.md, ISSUE 12)
    p.add_argument(
        "--pod-events", type=int,
        default=int(_env("TPU_POD_EVENTS", "512")),
        help="pod: capacity of the typed pod event ring served at "
        "GET /debug/events (per-kind counts export as "
        "pod_events_total regardless of ring size)",
    )
    # elastic pod (docs/configuration.md "Elastic pod", ISSUE 15):
    # live resharding + membership change on a running pod
    p.add_argument(
        "--pod-resize", choices=["on", "off"],
        default=_env("TPU_POD_RESIZE", "off"),
        help="pod: on = arm the elastic-membership plane — forwards "
        "stamp the topology epoch (wrong-epoch forwards are rejected "
        "rerouteable), the migrate/resize lane kinds serve, and "
        "POST /debug/pod/resize drives a live resize/add_host/"
        "drain_host with slice-by-slice migration and zero lost "
        "updates (an abort reverts to the old topology). off "
        "(default) = byte-identical PR 14 wire format and behavior",
    )
    # warm standby & fast join (docs/configuration.md "Warm standby &
    # fast join", ISSUE 18)
    p.add_argument(
        "--standby", choices=["on", "off"],
        default=_env("TPU_POD_STANDBY", "off"),
        help="pod: on = boot as a warm standby — form the host-local "
        "mesh, pre-compile the pow2 hit-bucket decision kernels, serve "
        "the peer lane, and wait for a coordinator's join_admin adopt "
        "(POST /debug/pod/join on any member promotes this host in "
        "under a second). Requires --pod-resize on wiring; off "
        "(default) = byte-identical PR 17 construction and wire "
        "format",
    )
    # tiered storage (docs/configuration.md "Tiered storage", ISSUE 17):
    # device-resident hot set over an exact host cold tier
    p.add_argument(
        "--tier-mode", choices=["on", "off"],
        default=_env("TPU_TIER_MODE", "off"),
        help="tpu: on = tiered counter storage — the device table "
        "serves the resident hot set, LRU evictions demote their exact "
        "cell (value + remaining window) to a host cold tier instead "
        "of dropping it, cold keys decide exactly on the host, and a "
        "TierManager thread migrates counters on observed heat priced "
        "against the fitted serving model (plain tpu storage only; "
        "GET /debug/tiering serves the live state). off (default) = "
        "byte-identical single-tier behavior",
    )
    p.add_argument(
        "--tier-cold", default=_env("TPU_TIER_COLD", ""),
        help="tiered: path of the cold tier's append-log disk spill "
        "(JSON lines, absolute cell state, last-row-wins; empty = "
        "no disk spill)",
    )
    p.add_argument(
        "--tier-migrate-interval", type=float,
        default=float(_env("TPU_TIER_MIGRATE_INTERVAL", "2.0")),
        help="tiered: seconds between TierManager migration rounds "
        "(each round drains the heat accumulators, prices candidates "
        "and runs the two-phase ledgered moves)",
    )
    # capacity controller (docs/configuration.md "Self-driving
    # capacity", ISSUE 20): one model-based loop over admission,
    # shedding, chunking, lease sizing AND pod membership
    p.add_argument(
        "--capacity-controller", choices=["on", "off", "observe"],
        default=_env("TPU_CTL_MODE", "off"),
        help="self-driving capacity (ISSUE 20): one model-based "
        "controller jointly actuates the admission AIMD ceiling, the "
        "deadline-shed priority floor, the ChunkPlanner target, the "
        "lease grant scale and pod membership (warm-standby join on "
        "sustained burn, tail-host drain on sustained idle). observe "
        "= compute and log every decision without actuating; off "
        "(default) = controller not constructed, byte-identical "
        "PR 18 behavior",
    )
    p.add_argument(
        "--ctl-interval", type=float,
        default=float(_env("TPU_CTL_INTERVAL_S", "1.0")),
        help="controller: seconds between control ticks",
    )
    p.add_argument(
        "--ctl-sustain", type=float,
        default=float(_env("TPU_CTL_SUSTAIN_S", "5.0")),
        help="controller: a membership proposal must hold its "
        "hysteresis band this long before actuating (leaving the "
        "band resets the clock)",
    )
    p.add_argument(
        "--ctl-dwell", type=float,
        default=float(_env("TPU_CTL_DWELL_S", "30.0")),
        help="controller: minimum seconds between membership "
        "actuations (with --ctl-sustain, what keeps diurnal ramps "
        "from flapping topology)",
    )
    p.add_argument(
        "--ctl-standby", default=_env("TPU_CTL_STANDBY", ""),
        help="controller: comma-separated peer-lane addresses of warm "
        "standbys (--standby on processes) the controller may promote "
        "on sustained burn; empty = membership grows unavailable",
    )
    p.add_argument(
        "--ctl-min-hosts", type=int,
        default=int(_env("TPU_CTL_MIN_HOSTS", "1")),
        help="controller: never drain the pod below this many hosts",
    )
    p.add_argument(
        "--ctl-max-hosts", type=int,
        default=int(_env("TPU_CTL_MAX_HOSTS", "8")),
        help="controller: never grow the pod above this many hosts",
    )
    p.add_argument(
        "--ctl-grow-headroom", type=float,
        default=float(_env("TPU_CTL_GROW_HEADROOM", "1.2")),
        help="controller: propose add_host while the model's capacity "
        "headroom ratio stays below this band",
    )
    p.add_argument(
        "--ctl-shrink-headroom", type=float,
        default=float(_env("TPU_CTL_SHRINK_HEADROOM", "3.0")),
        help="controller: propose drain_host while the headroom ratio "
        "stays above this band (the dead band between the two absorbs "
        "ramps)",
    )
    # pod fast path (docs/configuration.md "Pod fast path", ISSUE 13):
    # shard-aware native hot lane + lockstep psum lane for global limits
    p.add_argument(
        "--pod-psum-lane", choices=["on", "off"],
        default=_env("TPU_POD_PSUM_LANE", "off"),
        help="pod: on = fixed-window --global-namespaces limits are "
        "decided LOCALLY on every host against lockstep-exchanged "
        "remote partials (pod-wide psum) instead of pinning the whole "
        "namespace to one host; trades bounded over-admission (one "
        "exchange interval per remote host, like the reference's "
        "cached-Redis mode) for routed-share -> 1 on those namespaces. "
        "off (default) = exact namespace pinning. Every pod host must "
        "agree on this flag (the exchange is collective)",
    )
    p.add_argument(
        "--pod-psum-interval-ms", type=float,
        default=float(_env("TPU_POD_PSUM_INTERVAL_MS", "250")),
        help="pod: pacing of the lockstep psum exchange rounds (also "
        "the over-admission bound's time constant)",
    )
    p.add_argument(
        "--global-namespaces", default=_env("GLOBAL_NAMESPACES"),
        help="sharded: comma-separated namespaces whose counters are "
        "psum-replicated across shards (one budget mesh-wide)",
    )
    p.add_argument(
        "--global-region", type=int,
        default=int(_env("GLOBAL_REGION", "1024")),
        help="sharded: per-shard slots reserved for global counters",
    )
    p.add_argument(
        "--authority-listen", default=_env("AUTHORITY_LISTEN"),
        help="serve this process's counter storage as a shared authority "
        "for remote write-behind replicas (the out-of-process Redis role), "
        "e.g. 0.0.0.0:5101",
    )
    p.add_argument(
        "--authority-url", default=_env("AUTHORITY_URL"),
        help="cached: flush write-behind deltas to a remote authority "
        "(host:port of another server's --authority-listen) instead of a "
        "local disk store",
    )
    p.add_argument(
        "--batch-size", type=int,
        default=int(_env("REDIS_LOCAL_CACHE_BATCH_SIZE", "100")),
        help="cached: max deltas per authority flush (main.rs:651-658; "
        "default 100, redis/mod.rs:10-13)",
    )
    p.add_argument(
        "--flush-period", type=float,
        default=float(_env("REDIS_LOCAL_CACHE_FLUSHING_PERIOD_MS", "1000")),
        help="cached: write-behind flush period in MILLISECONDS, same "
        "unit as the flag's env var and the reference CLI "
        "(main.rs:664-674; default 1000)",
    )
    p.add_argument(
        "--max-cached", type=int, default=int(_env("MAX_CACHED", "10000")),
        help="cached: max locally cached counters (default 10000)",
    )
    p.add_argument(
        "--response-timeout", type=float,
        default=float(_env("RESPONSE_TIMEOUT", "350")),
        help="cached: remote-authority response timeout in MILLISECONDS "
        "(main.rs:684-691; default 350, redis/mod.rs:13); applies with "
        "--authority-url",
    )
    p.add_argument("--disk-path", default=_env("DISK_PATH"))
    p.add_argument(
        "--snapshot-path", default=_env("TPU_SNAPSHOT_PATH"),
        help="tpu: periodically checkpoint the counter table here and "
        "restore from it on startup",
    )
    p.add_argument(
        "--snapshot-period", type=float,
        default=float(_env("TPU_SNAPSHOT_PERIOD", "30")),
        help="tpu: seconds between counter-table checkpoints",
    )
    p.add_argument(
        "--peer", action="append", default=None,
        help="distributed/tpu: peer replication address (repeatable; with "
        "tpu storage this enables the replicated device-table topology)",
    )
    p.add_argument("--node-id", default=_env("NODE_ID"))
    p.add_argument(
        "--listen-address", default=_env("LISTEN_ADDRESS"),
        help="distributed: replication listen address",
    )
    p.add_argument(
        "--advertise-address", default=_env("ADVERTISE_ADDRESS"),
        help="distributed/tpu: address advertised to peers in gossip "
        "Hello/Membership packets (defaults to --listen-address; set it "
        "when binding 0.0.0.0 — e.g. the pod's stable DNS name — so "
        "peers learn a dialable URL)",
    )
    # admission plane (admission/controller.py)
    p.add_argument(
        "--admission-mode",
        choices=["off", "monitor", "enforce"],
        default=_env("ADMISSION_MODE", "off"),
        help="admission plane: off (default), monitor (breaker/failover "
        "active, sheds counted but not enforced), enforce (deadline/"
        "overload sheds enforced); requires a batched tpu storage",
    )
    p.add_argument(
        "--breaker-failures", type=int,
        default=int(_env("BREAKER_FAILURES", "3")),
        help="consecutive device-batch failures that open the "
        "device-plane circuit breaker",
    )
    p.add_argument(
        "--breaker-stall-ms", type=float,
        default=float(_env("BREAKER_STALL_MS", "2000")),
        help="an in-flight device batch older than this trips the "
        "breaker (the hung-device_sync failure mode)",
    )
    p.add_argument(
        "--breaker-reset-ms", type=float,
        default=float(_env("BREAKER_RESET_MS", "5000")),
        help="open-state dwell before a half-open device probe",
    )
    p.add_argument(
        "--max-inflight", type=int,
        default=int(_env("ADMISSION_MAX_INFLIGHT", "4096")),
        help="hard ceiling of the adaptive (AIMD) concurrency limit",
    )
    p.add_argument(
        "--admission-target-queue-ms", type=float,
        default=float(_env("ADMISSION_TARGET_QUEUE_MS", "20")),
        help="queue-wait target the AIMD limit steers toward; also the "
        "basis of deadline-aware shedding",
    )
    p.add_argument(
        "--shed-response",
        choices=["unavailable", "overlimit"],
        default=_env("SHED_RESPONSE", "unavailable"),
        help="RLS semantics of a shed: unavailable (gRPC UNAVAILABLE / "
        "HTTP 503, Envoy failure-mode decides) or overlimit "
        "(OVER_LIMIT / 429)",
    )
    p.add_argument(
        "--priority-key", default=_env("PRIORITY_KEY", "priority"),
        help="descriptor entry key carrying a request's priority class "
        "(low|normal|high|critical)",
    )
    p.add_argument(
        "--priority", action="append", default=None,
        help="namespace priority mapping NS=CLASS (repeatable); limits-"
        "file `priority:` annotations and the descriptor entry override "
        "per request",
    )
    p.add_argument(
        "--profile-dir",
        default=_env("TPU_PROFILE_DIR", "/tmp/limitador-tpu-profile"),
        help="default directory for on-demand jax.profiler captures "
        "(POST /debug/profile can override per capture)",
    )
    p.add_argument(
        "--native-trace-sample", type=int,
        default=int(_env("TPU_NATIVE_TRACE_SAMPLE", "0")),
        help="sample 1 in N hot-lane batches with a native trace id so "
        "OTLP device_batch spans carry the C-side phase splits for "
        "zero-Python rows (0 = off, the default)",
    )
    p.add_argument(
        "--native-slow-row-us", type=float,
        default=float(_env("TPU_NATIVE_SLOW_ROW_US", "50")),
        help="slow-row exemplar threshold of the native telemetry "
        "plane: a hot-lane begin averaging more than this many "
        "microseconds per row records a native phase breakdown + "
        "descriptor digest into the flight recorder (0 disables "
        "exemplars; histograms stay on)",
    )
    p.add_argument(
        "--slo-budget-ms", type=float,
        default=float(_env("TPU_SLO_BUDGET_MS", "2.0")),
        help="decision-latency SLO budget the burn-rate watchdog "
        "tracks at p99 over 5m/1h windows (slo_* gauges, /debug/stats "
        "slo section)",
    )
    p.add_argument(
        "--usage-topk", type=int,
        default=int(_env("TPU_USAGE_TOPK", "64")),
        help="heavy-hitter slots drained per pass by the tenant usage "
        "observatory (GET /debug/top, tenant_* metrics; 0 disables the "
        "observatory)",
    )
    p.add_argument(
        "--usage-drain-interval", type=float,
        default=float(_env("TPU_USAGE_DRAIN_S", "1.0")),
        help="seconds between heavy-hitter accumulator drains (also "
        "the control-signal timeline tick)",
    )
    p.add_argument(
        "--usage-near-threshold", type=float,
        default=float(_env("TPU_USAGE_NEAR_THRESHOLD", "0.9")),
        help="value/max_value utilization at which a sampled counter "
        "counts as near-exhaustion (tenant_near_exhaustion gauge)",
    )
    p.add_argument(
        "--model-fit",
        choices=["on", "off"],
        default=_env("TPU_MODEL_FIT", "on"),
        help="online serving-model observatory (ISSUE 14): fit the "
        "serving-model coefficients from live launch telemetry "
        "(model_*/capacity_* gauges, GET /debug/capacity, the "
        "model_r2/capacity_headroom_ratio/model_drift ControlSignals "
        "tail). 'off' detaches the ingest tap entirely",
    )
    p.add_argument(
        "--flight",
        choices=["on", "off"],
        default=_env("TPU_FLIGHT", "on"),
        help="flight recorder (ISSUE 16): always-on sampled decision "
        "exemplars + worst-K tails per lane, trigger engine (SLO burn, "
        "breaker open, resize abort, drift, device-probe fall, manual "
        "POST /debug/flight/trigger) persisting pod-correlated "
        "incident bundles (GET /debug/flight)",
    )
    p.add_argument(
        "--flight-sample", type=int,
        default=int(_env("TPU_FLIGHT_SAMPLE", "64")),
        help="flight recorder exemplar sampling stride: 1 in N "
        "decisions rings a full stage breakdown (worst-K tails are "
        "kept regardless; 1 records every decision)",
    )
    p.add_argument(
        "--flight-spool-dir",
        default=_env("TPU_FLIGHT_SPOOL", "/tmp/limitador-flight"),
        help="retention-capped directory incident bundles persist to "
        "(self-contained JSON, served back at GET /debug/flight)",
    )
    p.add_argument(
        "--flight-window", type=float,
        default=float(_env("TPU_FLIGHT_WINDOW_S", "10.0")),
        help="seconds of exemplar/signal history a fired bundle "
        "freezes (also the window peers contribute over)",
    )
    p.add_argument(
        "--flight-profile-s", type=float,
        default=float(_env("TPU_FLIGHT_PROFILE_S", "0.0")),
        help="bounded jax.profiler capture attached to automatic "
        "trigger fires, in seconds (0 = off; manual triggers opt in "
        "per request)",
    )
    p.add_argument(
        "--tracing-sample-rate", type=float,
        default=float(_env("TRACING_SAMPLE_RATE", "1.0")),
        help="head-sampling rate for exported spans: 1.0 records "
        "every request (the default, current behavior), 0.01 one in "
        "a hundred; the datastore_latency aggregation is never "
        "sampled",
    )
    p.add_argument(
        "--metrics-exemplars",
        choices=["on", "off"],
        default=_env("TPU_METRICS_EXEMPLARS", "off"),
        help="attach trace-id exemplars to tail-bucket "
        "datastore-latency observations and render /metrics in the "
        "OpenMetrics exposition (the only format carrying exemplars); "
        "off keeps the text 0.0.4 exposition byte-identical",
    )
    return p


def _try_restore(path, restore_fn, what: str):
    """Restore-or-None with rejected-checkpoint preservation (shared by
    the tpu and sharded branches)."""
    if not (path and os.path.exists(path)):
        return None
    try:
        storage = restore_fn(path)
    except Exception as exc:
        log.warning(
            f"snapshot {path} unreadable ({exc}); starting with a fresh "
            f"{what}")
        _preserve_rejected_snapshot(path)
        return None
    log.info(f"restored {what} from {path}")
    return storage


def _seed_from_sibling_snapshots(storage, base, owned, total_shards):
    """Slice-mapped restore after a membership change (ISSUE 15): the
    exact checkpoint for this host's owned shard range does not exist,
    so decode every sibling checkpoint (current ``.shards<lo>-<hi>``
    names AND legacy ``.host<id>`` ones) and seed ONLY the counters
    this host owns under the CURRENT topology, through apply_deltas
    (fresh windows, exact spends — the failover-replay accuracy
    contract). Disjoint by construction: every host filters to its own
    contiguous range, so a pod-wide rolling restart re-homes each slice
    exactly once."""
    import glob

    from ..routing import counter_key, stable_hash
    from ..tpu.sharded import snapshot_items

    lo, hi = owned
    files = sorted(
        set(glob.glob(base + ".shards*") + glob.glob(base + ".host*"))
    )
    files = [
        f for f in files
        if not (f.endswith(".rejected") or f.endswith(".tmp"))
    ]
    # Newest checkpoint first, and each counter seeds from exactly ONE
    # file: a live counter can appear in several files (a legacy
    # .host<id> left behind next to the .shards name that replaced it,
    # or stale files from a previous shard range) and applying it per
    # file would double its spend.
    files.sort(key=lambda f: os.path.getmtime(f), reverse=True)
    seeded = 0
    seen = set()
    for path in files:
        try:
            items = snapshot_items(path)
        except Exception as exc:
            log.warning(
                f"pod: sibling snapshot {path} undecodable ({exc}); "
                "skipped")
            continue
        mine = []
        for counter, value in items:
            key = counter_key(counter)
            if key in seen:
                continue
            if lo <= stable_hash(key) % total_shards < hi:
                seen.add(key)
                mine.append((counter, value))
        if not mine:
            continue
        try:
            storage.apply_deltas(mine)
            seeded += len(mine)
        except Exception as exc:
            log.warning(f"pod: seeding from {path} failed: {exc}")
    if seeded:
        log.info(
            f"pod: slice-mapped restore seeded {seeded} owned "
            f"counters from {len(files)} sibling checkpoint(s)")


def _preserve_rejected_snapshot(path: str) -> None:
    """A checkpoint we could not restore must be moved aside, NOT left in
    place: the fresh table's periodic snapshot loop would overwrite it,
    destroying counters that a correctly-configured restart could still
    recover."""
    rejected = path + ".rejected"
    try:
        os.replace(path, rejected)
        log.warning(f"preserved rejected snapshot as {rejected}")
    except OSError as exc:
        log.warning(f"could not preserve rejected snapshot: {exc}")


def _pod_local_mesh():
    """Pod mode: the sharded storage shards over THIS host's devices
    only (the default mesh would span the whole pod and every launch
    would be an SPMD program all hosts must enter together); the
    cross-host partition of the key space lives in the routed frontend
    (server/peering.py), not in the device mesh. None single-host —
    the storage's default mesh is already right there."""
    import jax

    if jax.process_count() > 1:
        from ..parallel import make_host_mesh

        return make_host_mesh()
    return None


def _pod_native_capable(args, log) -> bool:
    """Pod-mode native-pipeline capability check (ISSUE 13): the
    shard-aware hot lane is the only native plane that classifies
    foreign-owned keys, so pod mode serves the native pipeline ONLY
    when that lane can come up — ``--native-hot-lane on`` AND a built
    library exporting both the lane and the pod ownership mirror.
    Anything less warns and falls back to the routed compiled plane,
    the same warn-and-fallback shape as ``--native-hot-lane`` itself
    (never a hard refusal, never a silently wrong fast path)."""
    from .. import native as native_mod

    if args.native_hot_lane != "on":
        log.warning(
            "pod mode: --native-hot-lane off leaves the native pipeline "
            "without the shard-aware lane; serving through the routed "
            "compiled pipeline")
        return False
    if not native_mod.available():
        log.warning(
            "pod mode: native hostpath library unavailable; serving "
            "through the routed compiled pipeline")
        return False
    if not native_mod.pod_available():
        log.warning(
            "pod mode: native library lacks the pod ownership exports "
            "(stale binary — rebuild native/hostpath.cc); serving "
            "through the routed compiled pipeline")
        return False
    if args.plan_cache_size <= 0:
        log.warning(
            "pod mode: --plan-cache-size 0 disables the plan mirror "
            "the shard-aware lane rides; serving through the routed "
            "compiled pipeline")
        return False
    if args.pod_processes - 1 > 127 - native_mod.LANE_FOREIGN_BASE:
        log.warning(
            f"pod mode: {args.pod_processes} hosts exceed the native "
            "lane's int8 owner encoding (max "
            f"{128 - native_mod.LANE_FOREIGN_BASE}); serving through "
            "the routed compiled pipeline")
        return False
    return True


async def _discard_pipeline(pipeline):
    """Dispose a constructed-but-unserved NativeRlsPipeline (pod-mode
    fallback): its __init__ already wired eviction hooks on the live
    storage table — left attached they would call into an abandoned
    native context on every slot release for the process lifetime —
    and started its thread pools. Returns None for assignment."""
    table = pipeline.storage._table
    table.on_native_release = None
    table.on_slot_release = None
    table.on_clear = None
    try:
        await pipeline.close()
    except Exception:
        pass  # a half-built pipeline must not fail the fallback boot
    return None


def _compiles_kernels(args) -> bool:
    """Device storages, pod formation and the standby warm-up jit
    kernels; the host-only backends never import jax."""
    return bool(
        args.storage in ("tpu", "sharded")
        or args.standby == "on"
        or args.pod_processes > 1
        or args.pod_coordinator
    )


def _pin_platform() -> None:
    """Pin the jax backend per LIMITADOR_TPU_PLATFORM before anything
    initializes it: the way to serve the tpu storages from the CPU on
    purpose (``cpu``: accelerator-less validation, the container image)
    without touching jax's own JAX_PLATFORMS. Called before pod
    formation AND before the storage build — whichever runs first wins
    (idempotent)."""
    platform = os.environ.get("LIMITADOR_TPU_PLATFORM")
    if platform:
        import jax

        jax.config.update("jax_platforms", platform)


def _log_device(storage: str) -> None:
    """State which device the ``tpu``/``sharded`` storage got, and exit
    instead of serving from a CPU nobody asked for."""
    from ..device import require_accelerator

    device = require_accelerator(f"storage {storage!r}")
    log.info(
        f"device: platform {device['platform']}, kind {device['kind']}, "
        f"count {device['count']}")


def build_limiter(args, on_partitioned=None):
    """Limiter::new equivalent (main.rs:93-185): pick + build the backend.
    ``on_partitioned`` reaches storages that track authority partitions
    (the datastore_partitioned gauge)."""
    _pin_platform()
    if args.authority_url and args.storage != "cached":
        raise SystemExit(
            f"--authority-url only applies to the 'cached' storage "
            f"(got {args.storage!r}); run the replica as: "
            "... cached --authority-url HOST:PORT"
        )
    if args.storage == "memory":
        from ..storage.in_memory import DEFAULT_CACHE_SIZE, InMemoryStorage

        return RateLimiter(
            InMemoryStorage(args.cache_size or DEFAULT_CACHE_SIZE)
        )
    if args.storage == "tpu":
        _log_device(args.storage)
        from ..tpu.batcher import AsyncTpuStorage
        from ..tpu.storage import TpuStorage

        if args.peer or args.listen_address:
            # Replicated node: the constructor owns broker wiring, so the
            # checkpoint loads INTO the instance — restoring a plain
            # TpuStorage here would silently drop the node out of the
            # gossip mesh.
            from ..tpu.replicated import TpuReplicatedStorage

            storage = TpuReplicatedStorage(
                node_id=args.node_id or "node",
                listen_address=args.listen_address or "0.0.0.0:5001",
                advertise_address=args.advertise_address,
                peers=args.peer or [],
                capacity=args.tpu_capacity,
                cache_size=args.cache_size,
            )
            if args.snapshot_path and os.path.exists(args.snapshot_path):
                try:
                    storage.load_snapshot(args.snapshot_path)
                except Exception as exc:
                    log.warning(
                        f"snapshot {args.snapshot_path} unreadable "
                        f"({exc}); starting with a fresh replicated table")
                    _preserve_rejected_snapshot(args.snapshot_path)
                else:
                    log.info(
                        f"restored replicated counter table from "
                        f"{args.snapshot_path}")
        else:
            # Tiered storage (ISSUE 17): the facade is a TpuStorage, so
            # the whole fast path (plan cache, native hot lane, lease
            # tier) rides it unchanged; off (default) keeps the exact
            # single-tier construction below byte-identical.
            cls = TpuStorage
            if getattr(args, "tier_mode", "off") == "on":
                from ..tier import TieredStorage

                cls = TieredStorage
            storage = _try_restore(
                args.snapshot_path,
                lambda p: cls.restore(p, cache_size=args.cache_size),
                "counter table",
            )
            if storage is not None and storage._capacity != args.tpu_capacity:
                log.warning(
                    f"warning: snapshot capacity {storage._capacity} "
                    f"overrides --tpu-capacity {args.tpu_capacity}")
            if storage is None:
                if cls is TpuStorage:
                    storage = cls(
                        capacity=args.tpu_capacity,
                        cache_size=args.cache_size,
                    )
                else:
                    storage = cls(
                        capacity=args.tpu_capacity,
                        cache_size=args.cache_size,
                        spill_path=getattr(args, "tier_cold", "") or None,
                    )
            elif cls is not TpuStorage:
                # restore() has no spill knob; arm it post-restore
                storage._cold._spill_path = (
                    getattr(args, "tier_cold", "") or None
                )
        async_storage = AsyncTpuStorage(
            storage, max_delay=args.batch_delay_us / 1e6,
            dispatch_chunk=args.dispatch_chunk,
        )
        if args.pipeline in ("compiled", "native"):
            from ..tpu.pipeline import CompiledTpuLimiter

            return CompiledTpuLimiter(
                async_storage,
                plan_cache_size=getattr(args, "plan_cache_size", 1 << 16),
                dispatch_chunk=args.dispatch_chunk,
            )
        return AsyncRateLimiter(async_storage)
    if args.storage == "sharded":
        _log_device(args.storage)
        from ..tpu.batcher import AsyncTpuStorage  # noqa: lazy per-branch
        from ..tpu.sharded import TpuShardedStorage

        cli_global_ns = {
            ns for ns in (args.global_namespaces or "").split(",") if ns
        }
        mesh = _pod_local_mesh()
        storage = _try_restore(
            args.snapshot_path,
            lambda p: TpuShardedStorage.restore(
                p, mesh=mesh, cache_size=args.cache_size
            ),
            "sharded counter table",
        )
        if storage is not None:
            overrides = [
                (name, cli, snap)
                for name, cli, snap in (
                    ("--tpu-capacity", args.tpu_capacity,
                     storage._local_capacity),
                    ("--global-region", args.global_region,
                     storage._global_region),
                    ("--global-namespaces", cli_global_ns,
                     storage._global_ns),
                )
                if cli != snap
            ]
            for name, cli, snap in overrides:
                log.warning(
                    f"warning: snapshot {name}={snap!r} overrides the "
                    f"command line's {cli!r} (key routing must match "
                    "the checkpoint)")
        if storage is None:
            storage = TpuShardedStorage(
                mesh=mesh,
                local_capacity=args.tpu_capacity,
                cache_size=args.cache_size,
                global_namespaces=sorted(cli_global_ns),
                global_region=args.global_region,
            )
            # Slice-mapped restore (ISSUE 15): the exact checkpoint for
            # this host's CURRENT shard range is missing (first boot,
            # or the membership changed since the last checkpoint) —
            # re-key every sibling checkpoint and seed only the
            # counters this host owns now.
            if getattr(args, "_pod_snapshot_base", None):
                _seed_from_sibling_snapshots(
                    storage,
                    args._pod_snapshot_base,
                    args._pod_owned_shards,
                    args._pod_total_shards,
                )
        if getattr(args, "_pod_snapshot_meta", None):
            storage.snapshot_meta = args._pod_snapshot_meta
        async_storage = AsyncTpuStorage(
            storage, max_delay=args.batch_delay_us / 1e6,
            dispatch_chunk=args.dispatch_chunk,
        )
        if args.pipeline in ("compiled", "native"):
            if args.pipeline == "native":
                log.warning(
                    "native pipeline is single-chip only; using the "
                    "compiled pipeline with sharded storage")
            from ..tpu.pipeline import CompiledTpuLimiter  # noqa: lazy per-branch

            return CompiledTpuLimiter(
                async_storage,
                plan_cache_size=getattr(args, "plan_cache_size", 1 << 16),
                dispatch_chunk=args.dispatch_chunk,
            )
        return AsyncRateLimiter(async_storage)
    if args.storage == "disk":
        try:
            from ..storage.disk import DiskStorage
        except ImportError as exc:
            raise SystemExit(f"storage 'disk' unavailable: {exc}") from None

        path = args.disk_path or "limitador_counters.db"
        return RateLimiter(DiskStorage(path))
    if args.storage == "cached":
        from ..storage.cached import CachedCounterStorage

        if args.authority_url:
            from ..storage.authority import RemoteAuthority

            authority = RemoteAuthority(
                args.authority_url, timeout=args.response_timeout / 1000.0
            )
        else:
            from ..storage.disk import DiskStorage  # noqa: lazy per-branch

            authority = DiskStorage(args.disk_path or "limitador_counters.db")
        return AsyncRateLimiter(
            CachedCounterStorage(
                authority,
                flush_period=args.flush_period / 1000.0,
                batch_size=args.batch_size,
                max_cached=args.max_cached,
                on_partitioned=on_partitioned,
            )
        )
    if args.storage == "distributed":
        try:
            from ..storage.distributed import CrInMemoryStorage
        except ImportError as exc:
            raise SystemExit(
                f"storage 'distributed' unavailable: {exc}"
            ) from None

        return RateLimiter(
            CrInMemoryStorage(
                node_id=args.node_id or "node",
                listen_address=args.listen_address or "0.0.0.0:5001",
                advertise_address=args.advertise_address,
                peers=args.peer or [],
            )
        )
    raise SystemExit(f"unknown storage {args.storage!r}")


async def _amain(args) -> int:
    from ..observability import tracing as tracing_mod
    from ..observability.tracing import configure_tracing

    tracing_err = configure_tracing(args.tracing_endpoint)
    if tracing_err:
        log.warning(tracing_err)
    tracing_mod.set_sample_rate(args.tracing_sample_rate)
    if args.tracing_sample_rate < 1.0:
        log.info(
            f"tracing head sampling: {tracing_mod.sample_rate():.4f} "
            "(datastore_latency aggregation unsampled)")

    # Arm/disarm the serving-model fit BEFORE any storage construction:
    # DeviceStatsRecorder attaches its ingest tap at creation time
    # (set_metrics), so the flag must win over the ambient env first.
    from ..observability import model as model_mod

    model_mod.set_model_fit_enabled(args.model_fit == "on")

    # Persistent XLA compile cache, placed BEFORE pod formation / any
    # jit so every compile this process does lands in (or is served
    # from) the on-disk cache: a restarting host, a promoted standby and
    # the next boot all skip recompiling the pow2 bucket kernels.
    if _compiles_kernels(args):
        from ..device import enable_compile_cache

        log.info(f"XLA compile cache: {enable_compile_cache()}")

    # Pod formation MUST precede any storage/jax work: after
    # jax.distributed.initialize the device list is pod-global and the
    # sharded branch picks the host-local mesh off it. Snapshot and
    # failover state stay strictly per-host (each host checkpoints its
    # own shard block; a restarted host restores only its own).
    pod = None
    if args.pod_processes > 1 or args.pod_coordinator:
        # The platform pin must land BEFORE pod formation, not just in
        # build_limiter: initialize_pod's device discovery otherwise
        # probes every backend plugin first, and on an accelerator-less
        # box the TPU plugin's metadata retries can stall a pod host's
        # boot for minutes (whichever process loses the libtpu lockfile
        # race pays the slow probe).
        _pin_platform()
        if args.pod_processes > 1 and not args.pod_coordinator:
            raise SystemExit(
                "--pod-processes > 1 requires --pod-coordinator "
                "(env TPU_POD_COORDINATOR)"
            )
        if not (0 <= args.pod_process_id < args.pod_processes):
            raise SystemExit(
                f"--pod-process-id {args.pod_process_id} outside "
                f"[0, {args.pod_processes})"
            )
        from ..parallel import initialize_pod

        pod = initialize_pod(
            args.pod_coordinator, args.pod_processes, args.pod_process_id
        )
        log.info(
            f"pod formed: process {pod.process_id}/{pod.num_processes}, "
            f"{pod.local_device_count} local of "
            f"{pod.global_device_count} global devices")
        if args.snapshot_path:
            # Snapshot names are keyed by OWNED SHARD RANGE, not host
            # id (ISSUE 15): after a membership change the exact file
            # for the new range is missing and the sharded branch
            # re-keys every sibling checkpoint (including legacy
            # .host<id> names) through the slice-granular decode,
            # seeding only the counters this host owns under the NEW
            # topology — instead of silently loading the wrong host's
            # table (or refusing).
            sph = max(pod.local_device_count, 1)
            lo = pod.process_id * sph
            args._pod_snapshot_base = args.snapshot_path
            args._pod_owned_shards = (lo, lo + sph)
            args._pod_total_shards = pod.num_processes * sph
            args._pod_snapshot_meta = {
                "owned_shards": [lo, lo + sph],
                "topology": {
                    "hosts": pod.num_processes,
                    "host_id": pod.process_id,
                    "shards_per_host": sph,
                    "total_shards": pod.num_processes * sph,
                },
            }
            args.snapshot_path = (
                f"{args.snapshot_path}.shards{lo}-{lo + sph}"
            )
            log.info(
                f"pod: per-shard-range snapshot path "
                f"{args.snapshot_path}")

    initial_labels = args.metric_labels
    if args.metric_labels_file:
        try:
            with open(args.metric_labels_file) as f:
                content = f.read().strip()
            if content:
                initial_labels = content
        except OSError as exc:
            log.warning(
                f"metric labels file unreadable ({exc}); "
                "using --metric-labels")
    metrics = PrometheusMetrics(
        use_limit_name_label=args.limit_name_in_labels,
        metric_labels=initial_labels,
    )
    if args.metrics_exemplars == "on":
        metrics.enable_exemplars()
        log.info(
            "metrics exemplars on: /metrics renders the OpenMetrics "
            "exposition with trace-id exemplars on tail latency buckets")
    # Span-tree latency aggregation — the same two aggregates the
    # reference's subscriber registers (main.rs:908-917): request-path
    # datastore spans roll up under should_rate_limit, write-behind
    # authority I/O under flush_batcher_and_update_counters.
    from ..observability.metrics_layer import MetricsLayer, install

    install(
        MetricsLayer()
        .gather(
            "should_rate_limit",
            metrics.record_datastore_latency,
            ["datastore"],
        )
        .gather(
            "flush_batcher_and_update_counters",
            metrics.record_datastore_latency,
            ["datastore"],
        )
    )
    labels_watcher = None
    if args.metric_labels_file:

        def _load_labels(path):
            with open(path) as f:
                return f.read().strip()

        def _labels_changed(content):
            try:
                if content:
                    metrics.reload_labels(content)
                    log.info("metric labels reloaded")
            except Exception as exc:  # bad CEL must not kill the watcher
                log.warning(f"metric labels reload rejected: {exc}")

        labels_watcher = LimitsFileWatcher(
            args.metric_labels_file,
            _labels_changed,
            on_error=lambda exc: log.warning(
                f"metric labels file reload failed: {exc}"),
            loader=_load_labels,
            poll_interval=args.limits_poll_interval,
        )
        labels_watcher.start()
    limiter = build_limiter(
        args,
        on_partitioned=(
            lambda v: metrics.datastore_partitioned.set(1 if v else 0)
        ),
    )
    # Shard-aware routed frontend: wrap the limiter so every decision is
    # either locally owned (the collective-free lean path) or forwarded
    # ONCE over the peer lane to its owner host. Wrapping happens before
    # any consumer captures the limiter, so the RLS/HTTP planes, the
    # serving shards and the metrics wiring all see the routed surface.
    pod_frontend = None
    if pod is not None and pod.num_processes > 1:
        from ..routing import PodRouter, PodTopology
        from .peering import PeerLane, PodFrontend, PodResilience

        peer_urls = args.pod_peer or [
            u for u in (_env("TPU_POD_PEERS") or "").split(",") if u
        ]
        if len(peer_urls) != pod.num_processes:
            raise SystemExit(
                f"pod: need one --pod-peer per process "
                f"({pod.num_processes}), got {len(peer_urls)}"
            )
        # --pod-degraded-mode off pins the PR 10 posture exactly: no
        # retry, no breaker/failover — a peer failure fails that
        # request. Hedging stays its own opt-in (--pod-hedge-ms).
        degraded = args.pod_degraded_mode == "on"
        resilience = PodResilience(
            degraded=degraded,
            retry=degraded,
            hedge_ms=max(args.pod_hedge_ms, 0.0),
            breaker_failures=args.pod_peer_breaker_failures,
            breaker_reset_s=args.pod_peer_breaker_reset_ms / 1e3,
            probe_interval_s=float(_env("TPU_POD_PROBE_MS", "500")) / 1e3,
        )
        lane = PeerLane(
            pod.process_id,
            args.pod_peer_listen or f"{args.rls_host}:{args.rls_port + 2}",
            {
                i: url
                for i, url in enumerate(peer_urls)
                if i != pod.process_id
            },
            None,
            resilience=resilience,
        )
        # NOT started here: the lane begins serving only after the
        # initial limits load below — a restarting host must never
        # answer a forwarded decision against an empty limits set
        # (it would silently admit traffic its peers expect limited).
        router = PodRouter(PodTopology(
            hosts=pod.num_processes,
            host_id=pod.process_id,
            shards_per_host=max(pod.local_device_count, 1),
        ))
        pod_global_ns = {
            ns for ns in (args.global_namespaces or "").split(",") if ns
        }
        pod_frontend = PodFrontend(
            limiter, router, lane, global_namespaces=pod_global_ns,
            resilience=resilience,
            events_capacity=max(args.pod_events, 1),
        )
        limiter = pod_frontend
        log.info(
            f"pod routed ingress: host {pod.process_id} owns global "
            f"shards "
            f"[{pod.process_id * router.topology.shards_per_host}, "
            f"{(pod.process_id + 1) * router.topology.shards_per_host})")
        log.info(
            "pod resilience: degraded-owner failover "
            f"{'on' if degraded else 'off'}, hedge "
            f"{resilience.hedge_ms:.0f}ms, breaker "
            f"{resilience.breaker_failures} failures / "
            f"{resilience.breaker_reset_s * 1e3:.0f}ms reset")
        if args.pod_resize == "on":
            # Elastic pod (ISSUE 15): arm the live-resize plane.
            # Everything stays inert until POST /debug/pod/resize (or a
            # peer's resize proposal) drives a transition — except that
            # forwards now stamp the topology epoch and the wrong-owner
            # gate serves, which is the point of arming.
            from .resize import PodResizeCoordinator

            coordinator = PodResizeCoordinator(
                pod_frontend,
                peers={i: url for i, url in enumerate(peer_urls)},
                listen_address=peer_urls[pod.process_id],
                slice_pause_s=float(
                    _env("TPU_POD_RESIZE_SLICE_PAUSE_MS", "0") or 0
                ) / 1e3,
                transition_timeout_s=float(
                    _env("TPU_POD_RESIZE_TIMEOUT_S", "60") or 60
                ),
            )
            pod_frontend.attach_resize(coordinator)
            log.info(
                "elastic pod armed: POST /debug/pod/resize drives live "
                "resize/add_host/drain_host (topology epoch "
                f"{pod_frontend.router.topology_epoch})")
        if args.pod_psum_lane == "on" and pod_global_ns:
            # Lockstep psum lane (ISSUE 13): eligible fixed-window
            # global namespaces decide locally on EVERY host against
            # lockstep-exchanged remote partials instead of funneling
            # through one pin host. Attached before the initial limits
            # load so configure_with claims namespaces on first apply;
            # the pacer starts only then (all hosts reach the first
            # barrier with limits loaded).
            from ..parallel.mesh import PodPsumLane

            psum_lane = PodPsumLane(pod.num_processes, pod.process_id)
            pod_frontend.attach_psum_lane(psum_lane)
            psum_lane.start(
                interval_s=max(args.pod_psum_interval_ms, 10.0) / 1e3
            )
            log.info(
                "pod psum lane: lockstep exchange every "
                f"{max(args.pod_psum_interval_ms, 10.0):.0f}ms "
                f"(global namespaces: {sorted(pod_global_ns)})")
    if args.standby == "on":
        if pod_frontend is not None:
            log.warning(
                "--standby on ignored: this process already formed a "
                "pod (a member is not a standby)")
        else:
            # Warm standby (ISSUE 18): a single-host boot that forms
            # its host-local mesh, pre-compiles the pow2 hit-bucket
            # kernels and serves the peer lane memberless — hosts=1 /
            # host_id=0 is provisional, overwritten when a running
            # pod's join_host ships the real topology over the
            # join_admin lane kind.
            from ..routing import PodRouter, PodTopology  # noqa: lazy per-branch
            from .peering import PeerLane, PodFrontend, PodResilience  # noqa: lazy per-branch
            from .resize import PodResizeCoordinator  # noqa: lazy per-branch
            from .standby import WarmStandby

            degraded = args.pod_degraded_mode == "on"
            resilience = PodResilience(
                degraded=degraded,
                retry=degraded,
                hedge_ms=max(args.pod_hedge_ms, 0.0),
                breaker_failures=args.pod_peer_breaker_failures,
                breaker_reset_s=args.pod_peer_breaker_reset_ms / 1e3,
                probe_interval_s=float(
                    _env("TPU_POD_PROBE_MS", "500")
                ) / 1e3,
            )
            standby_listen = (
                args.pod_peer_listen
                or f"{args.rls_host}:{args.rls_port + 2}"
            )
            lane = PeerLane(
                0, standby_listen, {}, None, resilience=resilience,
            )
            router = PodRouter(PodTopology(
                hosts=1, host_id=0, shards_per_host=1,
            ))
            pod_frontend = PodFrontend(
                limiter, router, lane,
                global_namespaces={
                    ns for ns in
                    (args.global_namespaces or "").split(",") if ns
                },
                resilience=resilience,
                events_capacity=max(args.pod_events, 1),
            )
            limiter = pod_frontend
            coordinator = PodResizeCoordinator(
                pod_frontend,
                peers={},
                listen_address=standby_listen,
                transition_timeout_s=float(
                    _env("TPU_POD_RESIZE_TIMEOUT_S", "60") or 60
                ),
            )
            pod_frontend.attach_resize(coordinator)
            standby = WarmStandby(
                pod_frontend, coordinator,
                table_capacity=(
                    args.tpu_capacity
                    if args.storage in ("tpu", "sharded") else None
                ),
            )
            standby.warm()
            log.info(
                f"warm standby: peer lane at {standby_listen}, "
                "waiting for a coordinator's join "
                "(POST /debug/pod/join on any pod member)")
    counters_storage = limiter.storage.counters
    # Prefer the limiter (the compiled pipeline aggregates its storage's
    # stats and adds compiler eval counters); otherwise the storage itself.
    stats_source = (
        limiter if hasattr(limiter, "library_stats") else counters_storage
    )
    if hasattr(stats_source, "library_stats"):
        metrics.attach_library_source(stats_source)
    for target in (limiter, counters_storage):
        if hasattr(target, "set_metrics"):
            target.set_metrics(metrics)
            break
    # Native telemetry plane + SLO burn-rate watchdog (observability/
    # native_plane.py): arms the C-side histograms/exemplars, merges
    # them into /metrics on every render, feeds the watchdog from the
    # device-plane recorder and serves the /debug/stats sections.
    # Device storages only — host-only backends have no native lane to
    # measure (and should not pay a native build for a watchdog).
    native_plane = None
    if args.storage == "tpu":
        from ..observability.native_plane import NativePlane

        native_plane = NativePlane(
            budget_ms=args.slo_budget_ms,
            slow_row_us=args.native_slow_row_us,
            trace_sample=args.native_trace_sample,
        )
        # The recorder lives on whichever target set_metrics landed on:
        # the compiled limiter carries its own; the standard pipeline's
        # AsyncRateLimiter does not, so the storage's recorder is the
        # process flight recorder + SLO feed there.
        recorder = (
            getattr(limiter, "recorder", None)
            or getattr(counters_storage, "recorder", None)
        )
        if recorder is not None:
            native_plane.attach_recorder(recorder)
        metrics.attach_native_plane(native_plane)
    # Admission plane: overload control, priority shedding, device-plane
    # breaker + host failover (admission/). Only the batched TPU
    # storages expose set_admission — the host backends have no device
    # plane to fail over from.
    admission = None
    if args.admission_mode != "off":
        if not hasattr(counters_storage, "set_admission"):
            log.warning(
                f"--admission-mode {args.admission_mode} requires a "
                f"batched tpu storage (got {args.storage!r}); admission "
                "plane disabled")
        else:
            from ..admission import (
                AdaptiveLimiter,
                AdmissionController,
                CircuitBreaker,
                PriorityResolver,
            )

            admission = AdmissionController(
                mode=args.admission_mode,
                metrics=metrics,
                breaker=CircuitBreaker(
                    failure_threshold=args.breaker_failures,
                    stall_timeout=args.breaker_stall_ms / 1000.0,
                    reset_timeout=args.breaker_reset_ms / 1000.0,
                ),
                overload=AdaptiveLimiter(
                    max_inflight=args.max_inflight,
                    target_queue_wait=(
                        args.admission_target_queue_ms / 1000.0
                    ),
                ),
                priorities=PriorityResolver(
                    descriptor_key=args.priority_key,
                    namespace_map=PriorityResolver.parse_namespace_map(
                        args.priority or ()
                    ),
                ),
                shed_response=args.shed_response,
            )
            counters_storage.set_admission(admission)
            if hasattr(limiter, "fail_over_queued"):
                admission.add_drainable(limiter)
            admission.start(asyncio.get_running_loop())
            log.info(
                f"admission plane: mode={args.admission_mode}, "
                f"max-inflight={args.max_inflight}, breaker "
                f"stall={args.breaker_stall_ms:.0f}ms/"
                f"reset={args.breaker_reset_ms:.0f}ms, "
                f"shed-response={args.shed_response}")
    # gRPC server reflection is always on, from the vendored SDK-free
    # implementation (server/reflection.py) — the reference serves it
    # unconditionally too (envoy_rls/server.rs:232-263). The historical
    # --grpc-reflection-service flag is accepted and now a no-op.
    if args.grpc_reflection_service:
        log.info("grpc reflection is always enabled (vendored); "
                 "--grpc-reflection-service is a no-op")
    status = {"limits_file_version": 0, "limits_file_errors": 0}
    pipelines_to_invalidate = []

    async def apply_limits(limits):
        # AsyncRateLimiter and the pod frontend configure async; the
        # host-only backends are plain sync.
        applied = limiter.configure_with(limits)
        if inspect.isawaitable(applied):
            await applied
        for pipeline in pipelines_to_invalidate:
            pipeline.invalidate()
        if admission is not None:
            # Re-derive namespace priorities from `priority:` annotations.
            admission.priorities.refresh(limits)

    watcher = None
    if args.limits_file:
        loop = asyncio.get_running_loop()

        def on_change(limits):
            status["limits_file_version"] += 1
            fut = asyncio.run_coroutine_threadsafe(apply_limits(limits), loop)

            def _applied(f):
                exc = f.exception()
                if exc is not None:
                    # e.g. an edit adding a policy this storage rejects:
                    # keep serving the previous config, count the error.
                    status["limits_file_errors"] += 1
                    log.warning(f"limits reload rejected: {exc}")

            fut.add_done_callback(_applied)

        def on_error(exc):
            status["limits_file_errors"] += 1
            log.warning(f"limits file reload failed: {exc}")

        # Construct the watcher (capturing its baseline stamp) BEFORE the
        # initial load, so a file replaced between load and watch (e.g. a
        # ConfigMap symlink flip during startup) still triggers a reload.
        watcher = LimitsFileWatcher(
            args.limits_file, on_change, on_error,
            poll_interval=args.limits_poll_interval,
        )
        limits = load_limits_file(args.limits_file)
        try:
            await apply_limits(limits)
        except ValueError as exc:
            # e.g. a token_bucket limit on a storage whose cell format
            # can't count it — a config error, not a crash.
            raise SystemExit(f"limits file rejected: {exc}") from None
        status["limits_file_version"] = 1
        watcher.start()

    if pod_frontend is not None:
        # Limits are loaded (and the router configured) — the peer
        # lane may now answer forwarded decisions. Until this point
        # peers' forwards to this host fail fast (connection refused,
        # counted in their pod_peer_errors) instead of silently
        # admitting against an empty limits set.
        pod_frontend.lane.start()
        log.info(
            f"pod peer lane serving on "
            f"{pod_frontend.lane.listen_address} "
            f"(port {pod_frontend.lane.port})")

    native_pipeline = None
    if (
        pod_frontend is not None
        and args.storage == "tpu"
        and args.pipeline == "native"
        and not _pod_native_capable(args, log)
    ):
        # Capability check (ISSUE 13): the shard-aware hot lane is the
        # only native plane that routes foreign-owned keys, so pod mode
        # refuses the pipeline ONLY when that lane cannot serve (C
        # library absent/stale, or --native-hot-lane off) — the same
        # warn-and-fallback shape as --native-hot-lane itself.
        pass
    elif args.storage == "tpu" and args.pipeline == "native":
        from .. import native as native_mod

        if native_mod.available():
            from ..tpu.native_pipeline import NativeRlsPipeline

            native_pipeline = NativeRlsPipeline(
                limiter, metrics, max_delay=args.batch_delay_us / 1e6,
                plan_cache_size=args.plan_cache_size,
                dispatch_chunk=args.dispatch_chunk,
                hot_lane=args.native_hot_lane == "on",
            )
            if (
                args.native_hot_lane == "on"
                and not native_pipeline.hot_lane_active
            ):
                log.warning(
                    "native hot lane requested but unavailable (library "
                    "without lane symbols, or plan cache disabled); "
                    "serving through the pure-Python cached lane")
            if pod_frontend is not None:
                if native_pipeline.hot_lane_active:
                    # Pod fast path (ISSUE 13): the C mirror learns the
                    # topology, plans stamp their owner host, and the
                    # lane's bulk_decide handler decides forwarded blob
                    # batches — the zero-Python plane now serves pod
                    # mode. The pipeline's exact fallback is the pod
                    # frontend itself (limiter == pod_frontend here),
                    # so slow rows keep full routed semantics.
                    try:
                        pod_frontend.attach_pipeline(native_pipeline)
                    except RuntimeError as exc:
                        # e.g. a pod bigger than the int8 owner
                        # encoding — mis-routing is never an option.
                        log.warning(
                            f"pod mode: cannot arm the hot lane "
                            f"({exc}); serving through the routed "
                            "compiled pipeline")
                        native_pipeline = await _discard_pipeline(
                            native_pipeline)
                    else:
                        log.info(
                            "pod fast path: shard-aware native hot "
                            "lane on (foreign-owned rows bulk-forward "
                            "per flush)")
                else:
                    # Without the plan mirror the pipeline would decide
                    # against local storage only, bypassing the router.
                    log.warning(
                        "pod mode: the hot lane did not come up; "
                        "serving through the routed compiled pipeline")
                    native_pipeline = await _discard_pipeline(
                        native_pipeline)
            if native_pipeline is not None:
                pipelines_to_invalidate.append(native_pipeline)
                metrics.attach_library_source(native_pipeline)
            if admission is not None and native_pipeline is not None:
                admission.add_drainable(native_pipeline)
            if args.lease_mode == "on" and native_pipeline is not None:
                if native_pipeline.hot_lane_active:
                    from ..lease import LeaseConfig

                    try:
                        native_pipeline.attach_lease(LeaseConfig(
                            max_tokens=args.lease_max_tokens,
                        ))
                        log.info(
                            "limitador-tpu: quota-lease tier on "
                            f"(max {args.lease_max_tokens} tokens/lease)")
                    except RuntimeError as exc:
                        # e.g. a storage without the credit lane
                        # (sharded/global counters stay exact by design)
                        log.warning(
                            f"--lease-mode on unavailable: {exc}; "
                            "serving without the lease tier")
                else:
                    log.warning(
                        "--lease-mode on requires the native hot lane "
                        "(plan mirror); serving without the lease tier")
        else:
            log.warning(
                f"native hostpath unavailable "
                f"({native_mod.build_error()}); using compiled pipeline")

    if args.lease_mode == "on" and native_pipeline is None:
        log.warning(
            "--lease-mode on requires tpu storage with --pipeline native; "
            "serving without the lease tier")

    # Tenant usage observatory + unified control-signal bus (ISSUE 8):
    # periodic heavy-hitter drains with slot->counter attribution
    # (GET /debug/top, tenant_* families) and the joined ControlSignals
    # observation vector (GET /debug/signals, signal_* families) —
    # device-backed storages only (the accumulator lives in the device
    # table).
    observatory = None
    signal_bus = None
    device_storage = getattr(counters_storage, "inner", counters_storage)
    if args.usage_topk > 0 and hasattr(device_storage, "drain_hot_slots"):
        from ..observability.signals import SignalBus
        from ..observability.usage import TenantUsageObservatory

        signal_bus = SignalBus()
        signal_bus.warm()  # calibration probe off-thread
        observatory = TenantUsageObservatory(
            device_storage,
            pipeline=native_pipeline,
            top_k=args.usage_topk,
            interval_s=args.usage_drain_interval,
            near_threshold=args.usage_near_threshold,
            signal_bus=signal_bus,
        )
        bus_recorder = (
            getattr(limiter, "recorder", None)
            or getattr(counters_storage, "recorder", None)
        )
        if bus_recorder is not None:
            signal_bus.attach_recorder(bus_recorder)
        if admission is not None:
            signal_bus.attach_admission(admission)
        if native_pipeline is not None:
            signal_bus.attach_pipeline(native_pipeline)
        if native_plane is not None:
            signal_bus.attach_native_plane(native_plane)
        signal_bus.attach_observatory(observatory)
        metrics.attach_render_hook(observatory)
        metrics.attach_render_hook(signal_bus)
        observatory.start()
        log.info(
            f"tenant usage observatory: top-{args.usage_topk} drained "
            f"every {args.usage_drain_interval:.1f}s"
            + (", native leased merge on"
               if native_pipeline is not None else ""))

    # Pod observability plane (ISSUE 12): hop breakdown into the
    # process flight recorder + the pod_hop_phase_ms family, the local
    # ControlSignals bus federated over the lane, and the event
    # counters polled off library_stats (wired by PodFrontend itself).
    if pod_frontend is not None:
        pod_recorder = (
            getattr(limiter, "recorder", None)
            or getattr(counters_storage, "recorder", None)
        )
        if pod_recorder is not None:
            pod_frontend.attach_flight(pod_recorder)
        if signal_bus is not None:
            pod_frontend.attach_signal_bus(signal_bus)
        metrics.attach_render_hook(pod_frontend.hops)
        log.info(
            "pod observability plane: hop tracing, "
            f"{args.pod_events}-event timeline, federated signals "
            f"{'with' if signal_bus is not None else 'without'} the "
            "local signal bus")

    # Serving-model observatory (ISSUE 14): the online coefficient fit
    # over the recorder's per-launch observations, refit on the usage
    # observatory's drain thread, served at GET /debug/capacity and
    # joined into the ControlSignals tail. Device storages only — the
    # fit's observation unit is a device launch.
    model_estimator = None
    model_recorder = (
        getattr(limiter, "recorder", None)
        or getattr(counters_storage, "recorder", None)
    )
    if args.model_fit == "on" and model_recorder is not None:
        model_estimator = model_mod.process_estimator()
        model_estimator.budget_ms = args.slo_budget_ms
        # set_metrics predates the flag resolution in subprocess-spawn
        # orders; make the attachment explicit either way
        model_recorder.model = model_estimator
        model_estimator.attach_context(model_mod.pipeline_context(
            pipeline=native_pipeline, pod=pod_frontend,
            # sharded_launches lives on the STORAGE's library_stats
            # (merged by the batcher over the sharded pipeline) —
            # the native pipeline's stats never carry it
            storage=(
                counters_storage
                if hasattr(counters_storage, "library_stats") else None
            ),
        ))
        if pod_frontend is not None:
            events_log = getattr(pod_frontend, "events", None)
            if events_log is not None:
                model_estimator.attach_event_log(events_log)
        if signal_bus is not None:
            signal_bus.attach_model(model_estimator)
        if observatory is not None:
            observatory.model = model_estimator
        metrics.attach_render_hook(model_estimator)
        log.info(
            "serving-model observatory: online fit armed "
            f"(SLO budget {args.slo_budget_ms:.1f}ms, refit on the "
            "usage drain cadence; GET /debug/capacity)")

    # Flight recorder (ISSUE 16): always-on sampled exemplar rings +
    # worst-K tails on every decision lane, a trigger engine turning
    # SLO-burn/breaker/resize/drift/probe edges (and manual POST
    # /debug/flight/trigger) into self-contained incident bundles, and
    # pod-correlated peer ring collection over the peer lane.
    flight_engine = None
    if args.flight == "on":
        from ..observability.device_plane import (
            JaxProfiler as _FlightProfiler,
        )
        from ..observability.flight import (
            BundleSpool,
            FlightRecorder,
            TriggerEngine,
        )

        flight = FlightRecorder(
            sample_stride=max(args.flight_sample, 1),
            host_id=pod.process_id if pod is not None else 0,
        )
        flight.trace_provider = tracing_mod.current_trace_id
        flight_rec_target = (
            getattr(limiter, "recorder", None)
            or getattr(counters_storage, "recorder", None)
        )
        if flight_rec_target is not None:
            # The lean-lane tap: every batched decision the device
            # recorder times now offers the sampled stage breakdown.
            flight_rec_target.flight_tap = flight
        if pod_frontend is not None:
            pod_frontend.attach_flight_recorder(flight)
        flight_engine = TriggerEngine(
            flight,
            BundleSpool(args.flight_spool_dir),
            signals=signal_bus,
            events=(
                getattr(pod_frontend, "events", None)
                if pod_frontend is not None else None
            ),
            lane=pod_frontend.lane if pod_frontend is not None else None,
            profiler=(
                _FlightProfiler(args.profile_dir)
                if args.flight_profile_s > 0 else None
            ),
            window_s=args.flight_window,
            profile_s=args.flight_profile_s,
        )
        flight_engine.start()
        metrics.attach_render_hook(flight)
        log.info(
            "flight recorder armed: 1-in-"
            f"{max(args.flight_sample, 1)} exemplars + worst-K tails, "
            f"{args.flight_window:.0f}s bundle window, spool "
            f"{args.flight_spool_dir} (GET /debug/flight)")

    # Tiered storage (ISSUE 17): arm the migration thread over the
    # TieredStorage facade constructed in _build_limiter. Wired late so
    # it can see the lease broker (demotions settle outstanding tokens
    # first), the serving-model estimator (migration pricing), the pod
    # event log (tier_migration timeline) and the flight recorder (the
    # cold_tier decision lane).
    tier_manager = None
    if getattr(args, "tier_mode", "off") == "on":
        from ..tier import TieredStorage, TierManager

        tier_storage = getattr(counters_storage, "inner", counters_storage)
        if not isinstance(tier_storage, TieredStorage):
            log.warning(
                "--tier-mode on requires plain tpu storage (no "
                "peer/sharded mode); serving single-tier")
        else:
            tier_manager = TierManager(
                tier_storage,
                broker=(
                    native_pipeline.lease_broker
                    if native_pipeline is not None else None
                ),
                estimator=model_estimator,
                events=(
                    getattr(pod_frontend, "events", None)
                    if pod_frontend is not None else None
                ),
                observatory=observatory,
                interval_s=args.tier_migrate_interval,
            )
            if args.flight == "on":
                tier_storage.flight_tap = flight
            tier_manager.start()
            metrics.attach_render_hook(tier_manager)
            log.info(
                "tiered storage: device hot set over exact host cold "
                f"tier, migration every {args.tier_migrate_interval:.1f}s"
                + (
                    f", cold spill -> {args.tier_cold}"
                    if args.tier_cold else ""
                )
                + " (GET /debug/tiering)")

    # Capacity controller (ISSUE 20): one model-based loop jointly
    # actuating admission ceiling, shed floor, chunk target, lease
    # scale and pod membership. Wired last so the actuator binds every
    # live subsystem; off (the default) constructs nothing.
    capacity_controller = None
    if args.capacity_controller != "off":
        from ..control import (
            CapacityController,
            ModelPolicy,
            ServerActuator,
        )

        ctl_planners = []
        if hasattr(counters_storage, "_batcher_pairs"):
            for mb, _ub in counters_storage._batcher_pairs():
                cp = getattr(mb, "chunk_planner", None)
                if cp is not None:
                    ctl_planners.append(cp)
        cp = getattr(native_pipeline, "chunk_planner", None)
        if cp is not None:
            ctl_planners.append(cp)
        ctl_coordinator = (
            getattr(pod_frontend, "resize", None)
            if pod_frontend is not None else None
        )
        ctl_actuator = ServerActuator(
            overload=admission.overload if admission is not None else None,
            admission=admission,
            planners=ctl_planners,
            broker=(
                native_pipeline.lease_broker
                if native_pipeline is not None else None
            ),
            coordinator=ctl_coordinator,
            standby_addresses=[
                a.strip() for a in args.ctl_standby.split(",")
                if a.strip()
            ],
            min_hosts=args.ctl_min_hosts,
            max_hosts=args.ctl_max_hosts,
        )
        capacity_controller = CapacityController(
            ctl_actuator,
            policy=ModelPolicy(
                budget_ms=args.slo_budget_ms,
                grow_headroom=args.ctl_grow_headroom,
                shrink_headroom=args.ctl_shrink_headroom,
            ),
            signals=signal_bus,
            estimator=model_estimator,
            events=(
                getattr(pod_frontend, "events", None)
                if pod_frontend is not None else None
            ),
            mode=args.capacity_controller,
            interval_s=args.ctl_interval,
            sustain_s=args.ctl_sustain,
            dwell_s=args.ctl_dwell,
        )
        if signal_bus is not None:
            signal_bus.attach_controller(capacity_controller)
        metrics.attach_render_hook(capacity_controller)
        capacity_controller.start()
        log.info(
            "capacity controller "
            f"{'ON' if args.capacity_controller == 'on' else 'observing'}: "
            f"{len(ctl_actuator.specs())} knobs, membership "
            f"{'armed' if ctl_coordinator is not None else 'unavailable'}, "
            f"tick {args.ctl_interval:.1f}s, sustain "
            f"{args.ctl_sustain:.0f}s, dwell {args.ctl_dwell:.0f}s")

    authority_server = None
    if args.authority_listen:
        from ..storage.authority import serve_authority

        sync_storage = limiter.storage.counters
        inner = getattr(sync_storage, "inner", None)
        if inner is not None:
            sync_storage = inner  # AsyncTpuStorage -> the device table
        if not hasattr(sync_storage, "apply_deltas"):
            raise SystemExit(
                f"--authority-listen: storage {args.storage!r} cannot act "
                "as a shared authority (no apply_deltas)"
            )
        authority_server = serve_authority(sync_storage, args.authority_listen)
        log.info(
            f"limitador-tpu: shared authority on {args.authority_listen} "
            f"(port {authority_server.port})")

    native_ingress = None
    rls_grpc_port = args.rls_port
    if args.native_ingress:
        from ..native.ingress import (
            NativeIngress,
            ingress_available,
            ingress_build_error,
        )

        if native_pipeline is None:
            log.warning(
                "--native-ingress requires tpu storage with --pipeline "
                "native (and the native library); serving Python gRPC only")
        elif args.rate_limit_headers != "NONE":
            log.warning(
                "--native-ingress does not build response headers; use "
                "--rate-limit-headers NONE (serving Python gRPC only)")
        elif not ingress_available():
            log.warning(
                f"native ingress unavailable ({ingress_build_error()}); "
                "serving Python gRPC only")
        else:
            # Cold-path methods (Kuadrant check/report) route through the
            # same RlsService the Python gRPC server uses, so one port
            # serves the whole surface.
            from .rls import (
                _ENVOY_SERVICE,
                _KUADRANT_SERVICE,
                RlsService,
                make_native_method_handlers,
            )
            from .reflection import (
                REFLECTION_METHOD,
                native_reflection_handler,
            )

            ingress_service = RlsService(
                limiter, metrics, args.rate_limit_headers
            )
            ingress_handlers = make_native_method_handlers(ingress_service)
            ingress_handlers[REFLECTION_METHOD] = native_reflection_handler(
                (_ENVOY_SERVICE, _KUADRANT_SERVICE)
            )
            native_ingress = NativeIngress(
                native_pipeline,
                host=args.rls_host,
                port=args.rls_port,
                loop=asyncio.get_running_loop(),
                handlers=ingress_handlers,
                stream_path=REFLECTION_METHOD,
            )
            rls_grpc_port = args.rls_port + 1
            metrics.attach_library_source(native_ingress)

    rls_server = await serve_rls(
        limiter,
        f"{args.rls_host}:{rls_grpc_port}",
        metrics,
        args.rate_limit_headers,
        native_pipeline=native_pipeline,
        admission=admission,
    )
    # Extra serving shards: thread-per-event-loop gRPC servers on the
    # same port (SO_REUSEPORT). The limiter's per-loop batchers / submit
    # shards fan the accepted traffic into the one shared device lane.
    serving_shards = []
    if args.serving_shards > 1:
        from .rls import RlsServingShard

        for i in range(1, args.serving_shards):
            try:
                serving_shards.append(RlsServingShard(
                    i, limiter, f"{args.rls_host}:{rls_grpc_port}",
                    metrics, args.rate_limit_headers,
                    native_pipeline=native_pipeline, admission=admission,
                ))
            except RuntimeError as exc:
                log.warning(
                    f"serving shard {i} unavailable ({exc}); continuing "
                    f"with {1 + len(serving_shards)} shard(s)")
                break
        if serving_shards:
            log.info(
                f"serving shards: {1 + len(serving_shards)} event loops "
                f"on port {rls_grpc_port}")
    from ..observability.device_plane import JaxProfiler

    debug_sources = [counters_storage]
    if native_pipeline is not None:
        debug_sources.append(native_pipeline)
    if native_plane is not None:
        debug_sources.append(native_plane)
    if observatory is not None:
        debug_sources.append(observatory)
    if signal_bus is not None:
        debug_sources.append(signal_bus)
    if model_estimator is not None:
        debug_sources.append(model_estimator)
    if flight_engine is not None:
        debug_sources.append(flight_engine)
    if tier_manager is not None:
        debug_sources.append(tier_manager)
    if capacity_controller is not None:
        debug_sources.append(capacity_controller)
    http_runner = await run_http_server(
        limiter, args.http_host, args.http_port, metrics, status,
        debug_sources=debug_sources,
        profiler=JaxProfiler(args.profile_dir),
        admission=admission,
    )
    log.info(
        f"limitador-tpu: RLS gRPC on {args.rls_host}:{rls_grpc_port}"
        + (
            f", native HTTP/2 ingress on {args.rls_host}:{native_ingress.port}"
            if native_ingress is not None
            else ""
        )
        + f", HTTP on {args.http_host}:{args.http_port}, "
        f"storage={args.storage}")

    snapshot_task = None
    if args.storage in ("tpu", "sharded") and args.snapshot_path:
        tpu_storage = limiter.storage.counters.inner

        import threading

        snapshot_mutex = threading.Lock()

        def take_snapshot():
            # Serializes periodic vs shutdown snapshots: cancelling the loop
            # task cannot stop an executor thread mid-write, and two writers
            # on one tmp file would publish a corrupt checkpoint.
            with snapshot_mutex:
                tmp = args.snapshot_path + ".tmp"
                tpu_storage.snapshot(tmp)
                os.replace(tmp, args.snapshot_path)

        async def snapshot_loop():
            while True:
                await asyncio.sleep(args.snapshot_period)
                try:
                    await asyncio.get_running_loop().run_in_executor(
                        None, take_snapshot
                    )
                except Exception as exc:
                    # A failed checkpoint (disk full, ...) must not end
                    # periodic checkpointing for the process lifetime.
                    log.warning(f"snapshot failed: {exc}")

        snapshot_task = asyncio.get_running_loop().create_task(snapshot_loop())

    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        try:
            loop.add_signal_handler(sig, stop.set)
        except NotImplementedError:
            pass
    await stop.wait()

    if snapshot_task is not None:
        # Drain any in-flight periodic snapshot before the final one — two
        # writers on the same tmp file would publish a corrupt checkpoint.
        snapshot_task.cancel()
        try:
            await snapshot_task
        except asyncio.CancelledError:
            pass
        try:
            await asyncio.get_running_loop().run_in_executor(
                None, take_snapshot
            )
        except Exception as exc:
            log.warning(f"final snapshot failed: {exc}")

    if watcher:
        watcher.stop()
    if labels_watcher is not None:
        labels_watcher.stop()
    if authority_server is not None:
        authority_server.stop()
    if native_ingress is not None:
        native_ingress.close()
    for shard in serving_shards:
        # Off-loop: shard.stop blocks on the sync server's drain and a
        # thread join; inline it would freeze the aio server's own
        # graceful stop behind a wedged shard.
        await asyncio.get_running_loop().run_in_executor(
            None, shard.stop, 1.0
        )
    await rls_server.stop(grace=1.0)
    await http_runner.cleanup()
    if capacity_controller is not None:
        # First: nothing may actuate (or propose a resize) into
        # subsystems that are shutting down behind it.
        capacity_controller.close()
    if observatory is not None:
        observatory.close()
    if tier_manager is not None:
        # Before the pipeline/storage close: the last round may still
        # settle leases and drain the cold journal to the spill log.
        tier_manager.close()
    if flight_engine is not None:
        flight_engine.stop()
    if admission is not None:
        await admission.close()
    if native_pipeline is not None:
        await native_pipeline.close()
    if pod_frontend is not None:
        pod_frontend.close_pod()
        limiter = pod_frontend._limiter  # close the wrapped limiter
    if hasattr(limiter, "close"):
        # Compiled pipeline: final flush + drain in-flight collects +
        # release worker pools before the storage goes away.
        await limiter.close()
    if isinstance(limiter, AsyncRateLimiter):
        await limiter.storage.counters.close()
    return 0


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    _setup_logging(args.structured_logs, args.log_level)
    if args.validate:
        if not args.limits_file:
            log.error("--validate requires a limits file")
            return 2
        try:
            limits = load_limits_file(args.limits_file)
        except LimitsFileError as exc:
            log.error(f"INVALID: {exc}")
            return 1
        # Success goes to STDOUT (script-parseable contract, independent
        # of the log format); diagnostics ride the stderr log handler.
        print(f"OK: {len(limits)} limits")
        return 0
    try:
        return asyncio.run(_amain(args))
    except KeyboardInterrupt:
        return 0
    except (ValueError, LimitsFileError, CelError) as exc:
        log.error(f"configuration error: {exc}")
        return 2


if __name__ == "__main__":
    sys.exit(main())
