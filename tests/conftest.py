"""Test configuration.

Tests run on CPU with 8 virtual XLA devices so that the TPU backend and the
multi-chip sharding paths are exercised without TPU hardware. Must run
before jax is imported.
"""

import os
import sys

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# The suite runs on the 8-device virtual CPU mesh unless the invoking
# shell names another platform (the kernel tests also pass on one real
# chip: JAX_PLATFORMS=tpu python -m pytest tests/test_kernel.py). Server
# subprocesses inherit the choice, so they serve from the CPU by name.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# Persistent compilation cache: repeated test runs skip XLA recompiles.
from limitador_tpu.device import enable_compile_cache  # noqa: E402

enable_compile_cache()

import pytest  # noqa: E402

# Every env var the server CLI layers under its flags
# (limitador_tpu/server/__main__.py `_env(...)` defaults). Fixtures that
# spawn server subprocesses must scrub these so a test's behavior never
# depends on what leaked into the invoking shell — the r4 reflection e2e
# only passed because TPU_NATIVE_INGRESS=1 was ambient.
SERVER_ENV_VARS = frozenset({
    "LIMITS_FILE", "STORAGE", "ENVOY_RLS_HOST", "ENVOY_RLS_PORT",
    "HTTP_API_HOST", "HTTP_API_PORT", "LIMIT_NAME_IN_PROMETHEUS_LABELS",
    "TRACING_ENDPOINT", "METRIC_LABELS", "METRIC_LABELS_FILE",
    "RATE_LIMIT_HEADERS", "STRUCTURED_LOGS", "LIMITADOR_LOG", "RUST_LOG",
    "LIMITS_FILE_POLL_INTERVAL", "TPU_TABLE_CAPACITY", "TPU_BATCH_DELAY_US",
    "TPU_DISPATCH_CHUNK",
    "TPU_PIPELINE", "TPU_NATIVE_INGRESS", "GLOBAL_NAMESPACES",
    "GLOBAL_REGION", "AUTHORITY_LISTEN", "AUTHORITY_URL",
    "REDIS_LOCAL_CACHE_BATCH_SIZE", "REDIS_LOCAL_CACHE_FLUSHING_PERIOD_MS",
    "MAX_CACHED", "RESPONSE_TIMEOUT", "DISK_PATH", "TPU_SNAPSHOT_PATH",
    "TPU_SNAPSHOT_PERIOD", "NODE_ID", "LISTEN_ADDRESS",
    "ADVERTISE_ADDRESS", "LIMITADOR_TPU_PLATFORM",
    "ADMISSION_MODE", "BREAKER_FAILURES", "BREAKER_STALL_MS",
    "BREAKER_RESET_MS", "ADMISSION_MAX_INFLIGHT",
    "ADMISSION_TARGET_QUEUE_MS", "SHED_RESPONSE", "PRIORITY_KEY",
    "TPU_NATIVE_TRACE_SAMPLE", "TPU_NATIVE_SLOW_ROW_US",
    "TPU_SLO_BUDGET_MS",
    "TPU_USAGE_TOPK", "TPU_USAGE_DRAIN_S", "TPU_USAGE_NEAR_THRESHOLD",
    # an ambient sanitizer variant would silently slow every native
    # budget test 2-20x (and a server subprocess would rebuild the .so)
    "TPU_NATIVE_SANITIZE",
    # ambient pod topology would make a spawned server call
    # jax.distributed.initialize and hang waiting for a coordinator
    "TPU_POD_COORDINATOR", "TPU_POD_PROCESSES", "TPU_POD_PROCESS_ID",
    "TPU_POD_PEERS", "TPU_POD_PEER_LISTEN",
    # pod resilience plane (ISSUE 11): ambient fault injection or
    # breaker/hedge tuning would silently reshape any pod-spawning test
    "TPU_POD_DEGRADED_MODE", "TPU_POD_HEDGE_MS",
    "TPU_POD_PEER_BREAKER_FAILURES", "TPU_POD_PEER_BREAKER_RESET_MS",
    "TPU_POD_PROBE_MS", "TPU_POD_FAULTS", "TPU_POD_FAULT_SEED",
    "TPU_POD_FAULT_DELAY_MS",
    # pod observability plane (ISSUE 12): an ambient event-ring cap
    # would silently reshape /debug/events assertions
    "TPU_POD_EVENTS",
    # serving-model observatory (ISSUE 14): an ambient off would 404
    # every /debug/capacity assertion in a spawned server
    "TPU_MODEL_FIT",
    # elastic pod (ISSUE 15): ambient arming or chaos pauses would
    # silently reshape any pod-spawning test's wire format and timing
    "TPU_POD_RESIZE", "TPU_POD_RESIZE_SLICE_PAUSE_MS",
    "TPU_POD_RESIZE_TIMEOUT_S",
    # tiered storage (ISSUE 17): ambient tiering would silently swap
    # the storage class (and migration timing) under any spawned server
    "TPU_TIER_MODE", "TPU_TIER_COLD", "TPU_TIER_MIGRATE_INTERVAL",
    # warm standby & fast join (ISSUE 18): an ambient standby flag would
    # boot a memberless coordinator instead of the configured pod
    "TPU_POD_STANDBY",
    # capacity controller (ISSUE 20): an ambient controller would
    # actuate knobs (or membership!) under any spawned server a test
    # is timing or byte-pinning
    "TPU_CTL_MODE", "TPU_CTL_INTERVAL_S", "TPU_CTL_SUSTAIN_S",
    "TPU_CTL_DWELL_S", "TPU_CTL_STANDBY", "TPU_CTL_MIN_HOSTS",
    "TPU_CTL_MAX_HOSTS", "TPU_CTL_GROW_HEADROOM",
    "TPU_CTL_SHRINK_HEADROOM",
})


def server_env(repo_root, **extra):
    """Environment for a spawned `limitador_tpu.server` subprocess: the
    ambient environment minus every server config var (so only the flags
    the test passes explicitly shape the server), plus PYTHONPATH and any
    explicit overrides."""
    env = {k: v for k, v in os.environ.items() if k not in SERVER_ENV_VARS}
    env["PYTHONPATH"] = str(repo_root)
    env.update({k: str(v) for k, v in extra.items()})
    return env


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running chaos/soak tests"
    )


@pytest.fixture
def fake_clock():
    """Controllable clock so window-expiry tests don't sleep."""

    class _Clock:
        def __init__(self):
            self.now = 1_700_000_000.0

        def __call__(self):
            return self.now

        def advance(self, seconds):
            self.now += seconds

    return _Clock()
