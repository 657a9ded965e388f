"""Warm standby: sub-second host join (ISSUE 18).

A cold host joining a pod pays three serial costs before it answers
its first decision: mesh/device formation, XLA compilation of the
decision kernels, and limits configuration. The warm standby pays all
three BEFORE it is a member, so the join itself (server/resize.py
``join_host``) flips membership as a pure control-plane fact:

* **mesh** — the standby forms its HOST-LOCAL mesh at boot
  (``parallel.make_host_mesh``): since ISSUE 18 membership is not a
  `jax.distributed` formation property, so a single process can form,
  compile and serve without knowing which pod it will land in.
* **kernels** — :meth:`WarmStandby.warm` drives the jitted decision
  kernels through every power-of-two hit bucket the batcher can emit
  (``tpu/storage._bucket`` pads hit counts to pow2 precisely so there
  are few programs to compile), against a scratch table of the SAME
  capacity the serving storage uses — jit caches key on shapes, so a
  mismatched capacity would compile programs the serving path never
  reuses. The programs also persist in the compile cache
  (``device.enable_compile_cache``), so even the standby's own warm-up
  is fast after its first boot.
* **state** — the coordinator ships limits + the plan-cache seed over
  the ``join_admin``/``plan_seed`` lane kinds (armed here) before any
  routing changes, and the PR 15 migrate lane moves the joiner's shard
  slice AFTER the epoch bump, overlapped with serving.

``--standby off`` (the default) never constructs a WarmStandby and
never arms the join callbacks: wire format and construction stay
byte-identical to PR 17 (test-pinned).
"""

from __future__ import annotations

import logging
import time
from typing import Optional, Sequence

__all__ = ["WarmStandby", "METRIC_FAMILIES", "DEFAULT_WARM_BUCKETS"]

log = logging.getLogger("limitador_tpu.pod.standby")

#: metric families this module owns (cross-checked against
#: observability/metrics.py by the analysis registry pass)
METRIC_FAMILIES = (
    "standby_ready",
    "standby_warm_kernels",
    "standby_warm_seconds",
)

#: the pow2 hit buckets warmed by default: ``_bucket`` floors at 8 and
#: the batcher's adaptive chunking tops out well under 512 hits per
#: kernel launch in every shipped configuration
DEFAULT_WARM_BUCKETS = (8, 16, 32, 64, 128, 256, 512)


class WarmStandby:
    """Holds a formed, compiled, configured-but-memberless host ready
    for :meth:`PodResizeCoordinator.join_host` promotion.

    Wiring (``--standby on`` in server/__main__.py, or a test/bench
    harness): construct over the assembled frontend + coordinator,
    call :meth:`warm` once off the serving path, and the standby waits
    for a coordinator's ``join_admin`` adopt. Arming is explicit and
    separate from ``attach_resize`` so the default pod construction
    stays byte-identical to PR 17."""

    def __init__(
        self,
        frontend,
        coordinator,
        warm_buckets: Sequence[int] = DEFAULT_WARM_BUCKETS,
        table_capacity: Optional[int] = None,
    ):
        self.frontend = frontend
        self.coordinator = coordinator
        self.warm_buckets = tuple(
            sorted({int(b) for b in warm_buckets})
        )
        # jit programs key on the table shape: warm against the SAME
        # capacity the serving storage holds or the compiles are wasted
        if table_capacity is None:
            storage = getattr(frontend, "pipeline", None)
            storage = getattr(storage, "storage", None) or getattr(
                frontend._limiter, "storage", None
            )
            storage = getattr(storage, "counters", storage)
            table_capacity = getattr(storage, "capacity", None)
        self.table_capacity = int(table_capacity or 1024)
        self.ready = False
        self.warm_kernels = 0
        self.warm_seconds = 0.0
        # the join control plane: the coordinator answers adopt/limits
        # ops, the frontend imports shipped plan seeds, and the
        # frontend's library_stats carries the standby_* families
        frontend.lane.join_cb = coordinator.handle_join
        frontend.lane.plan_seed_cb = frontend.plan_seed_import
        frontend.standby = self

    def warm(self) -> dict:
        """Pre-compile the decision kernels at every configured pow2
        hit bucket (blocking; run at boot, never on a serving loop).
        Warm-up failure degrades to cold-compile-on-first-miss — it
        must never prevent the standby from becoming joinable."""
        started = time.time()
        compiled = 0
        try:
            compiled = self._compile_buckets()
        except Exception as exc:
            log.warning(f"standby kernel warm-up failed: {exc}")
        self.warm_seconds = round(time.time() - started, 6)
        self.warm_kernels = compiled
        self.ready = True
        self.frontend.events.emit(
            "standby_ready",
            kernels=compiled,
            buckets=len(self.warm_buckets),
            seconds=self.warm_seconds,
            capacity=self.table_capacity,
        )
        log.info(
            f"warm standby ready: {compiled} kernels over buckets "
            f"{list(self.warm_buckets)} in {self.warm_seconds:.3f}s "
            f"(table capacity {self.table_capacity})"
        )
        return {
            "ready": True,
            "kernels": compiled,
            "seconds": self.warm_seconds,
        }

    def _compile_buckets(self) -> int:
        import jax
        import jax.numpy as jnp
        import numpy as np

        from ..ops import kernel as K

        cap = self.table_capacity
        pad_max = np.int32(np.iinfo(np.int32).max)
        # check_and_update_batch and update_batch donate their state:
        # thread ONE scratch table through every launch (its shape —
        # the jit cache key that must match serving — is (capacity+1,)
        # regardless of the hit bucket)
        state = K.make_table(cap)
        compiled = 0
        for H in self.warm_buckets:
            # an all-padding batch: slot C, delta 0, max INT32_MAX —
            # the exact inert row contract check_and_update_impl
            # documents, so warming mutates nothing
            slots = jnp.full((H,), cap, jnp.int32)
            zeros = jnp.zeros((H,), jnp.int32)
            maxes = jnp.full((H,), pad_max, jnp.int32)
            windows = jnp.ones((H,), jnp.int32)
            off = jnp.zeros((H,), bool)
            now = jnp.int32(0)
            state, result = K.check_and_update_batch(
                state, slots, zeros, maxes, windows, zeros, off, off,
                now,
            )
            jax.block_until_ready(result.admitted)  # noqa: warm-up helper — boot-time compile drain, never the decision path
            compiled += 1
            state = K.update_batch(
                state, slots, zeros, windows, off, off, now
            )
            jax.block_until_ready(state.values)  # noqa: warm-up helper — boot-time compile drain, never the decision path
            compiled += 1
        return compiled

    def stats(self) -> dict:
        """The ``standby_*`` family feed (merged into library_stats by
        the server wiring when ``--standby on``)."""
        return {
            "standby_ready": 1 if self.ready else 0,
            "standby_warm_kernels": self.warm_kernels,
            "standby_warm_seconds": self.warm_seconds,
        }

    def status(self) -> dict:
        """The ``GET /debug/pod/standby`` payload."""
        return {
            **self.stats(),
            "buckets": list(self.warm_buckets),
            "table_capacity": self.table_capacity,
            "host": self.coordinator.host_id,
            "topology_epoch": self.coordinator.router.topology_epoch,
            "join_ttfd_seconds": self.coordinator.join_ttfd_seconds,
        }
