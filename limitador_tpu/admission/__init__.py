"""Admission plane: overload control, priority shedding, TPU failover.

The decision-path guardian between the serving plane (gRPC/HTTP
handlers) and the storage/TPU plane. A device call can hang: the
device plane can vanish for minutes, and a serving path with no concept
of an unhealthy backend then blocks every batched decision behind one
stalled ``device_sync``. Three cooperating pieces fix that:

* :mod:`breaker` — a device-plane health monitor + circuit breaker
  (closed/open/half-open) fed by batch outcomes and a stalled-batch
  watchdog. On trip the check path fails over to the exact host
  oracle (:mod:`limitador_tpu.storage.failover`); on recovery the
  host-accumulated deltas reconcile back into the device table
  through the existing ``apply_deltas`` contract.
* :mod:`overload` — an AIMD adaptive concurrency limit driven by the
  queue-wait signal the PR-1 histograms measure, plus a queue-wait
  estimate for deadline-aware shedding: a request whose gRPC deadline
  cannot survive the current queue wait is rejected before it
  occupies a batch slot.
* :mod:`priority` — request priority classes resolved from descriptor
  entries and limits-file annotations, so sheds take low-priority
  traffic first.

:class:`AdmissionController` (:mod:`controller`) ties them together and
is what the serving plane talks to.
"""

from .breaker import BreakerState, CircuitBreaker
from .controller import AdmissionController, AdmissionShed
from .overload import AdaptiveLimiter
from .priority import (
    DEFAULT_PRIORITY,
    PRIORITIES,
    PriorityResolver,
    priority_level,
)

__all__ = [
    "ADMISSION_MODES",
    "METRIC_FAMILIES",
    "SHED_REASONS",
    "AdmissionController",
    "AdmissionShed",
    "AdaptiveLimiter",
    "BreakerState",
    "CircuitBreaker",
    "DEFAULT_PRIORITY",
    "PRIORITIES",
    "PriorityResolver",
    "priority_level",
]

#: --admission-mode values: off = subsystem not constructed; monitor =
#: breaker/failover active, sheds COUNTED but not enforced; enforce =
#: sheds enforced too.
ADMISSION_MODES = ("off", "monitor", "enforce")

#: Why a request was shed before batch admission. ``controller`` =
#: the capacity controller's shed floor (ISSUE 20) put this request's
#: priority class below the line.
SHED_REASONS = ("deadline", "overload", "controller")

#: Prometheus families this subsystem writes (observability/metrics.py
#: declares them; ``tools/lint.py``'s registry lint cross-checks this
#: tuple against the declarations so the two can never drift).
METRIC_FAMILIES = (
    "admission_inflight",
    "admission_limit",
    "admission_sheds",
    "admission_breaker_state",
    "admission_breaker_transitions",
    "admission_failover_decisions",
    "admission_failover_seconds",
    "admission_reconciled_deltas",
)
