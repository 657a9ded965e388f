"""The process's jax backend: which device serves, the refusal to serve
from the CPU unasked, and where compiled programs persist.

Importing this module imports no jax — the functions do, so host-only
backends (memory/disk/cached) and jax-free clients (``chip_smoke.py``)
can use the path helpers without paying for, or holding, a device.
"""

from __future__ import annotations

import os
import sys
from typing import Optional

__all__ = [
    "compile_cache_dir",
    "enable_compile_cache",
    "device_report",
    "cpu_unasked",
    "require_accelerator",
]

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def compile_cache_dir() -> str:
    """Where this checkout's compiled programs persist:
    ``JAX_COMPILATION_CACHE_DIR`` when the environment places the cache,
    else ``<checkout>/.jax_cache`` — a fixed path, because a cache that
    moves between boots never hits."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        _CHECKOUT, ".jax_cache"
    )


def enable_compile_cache() -> str:
    """Persist every compile of this process at :func:`compile_cache_dir`
    (call before the first jit). When the environment names the
    directory jax reads it itself and none is set in code. Both
    persistence thresholds go to zero: the decision kernels are a fleet
    of small pow2-bucket programs, exactly what the defaults skip."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return compile_cache_dir()


def device_report() -> Optional[dict]:
    """Platform, device kind and device count as jax reports them; None
    when this process never imported jax (a diagnostics read must not
    make a host-only server take a device)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def cpu_unasked(platform: str, jax_platforms: Optional[str]) -> bool:
    """True when the backend is the CPU and nobody asked for it by name.
    Asked-for means ``jax_platforms`` names ``cpu`` — where both
    ``JAX_PLATFORMS=cpu`` and ``LIMITADOR_TPU_PLATFORM=cpu`` end up."""
    asked = "cpu" in (jax_platforms or "").split(",")
    return platform == "cpu" and not asked


def require_accelerator(what: str) -> dict:
    """The device report for a component that exists to run on an
    accelerator; exits non-zero when jax fell back to the CPU unasked
    (chip missing, or held by another process) instead of letting the
    component look healthy while the host does the work."""
    import jax

    report = device_report()
    if cpu_unasked(report["platform"], jax.config.jax_platforms):
        raise SystemExit(
            f"{what}: jax found no accelerator and fell back to platform "
            f"cpu ({report['kind']} x{report['count']}); refusing to serve "
            "from the CPU unasked. Free the chip, or set JAX_PLATFORMS=cpu "
            "(or LIMITADOR_TPU_PLATFORM=cpu) to serve from the CPU on "
            "purpose."
        )
    return report
