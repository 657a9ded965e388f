"""Exact in-memory counter storage — the parity oracle.

Mirrors /root/reference/limitador/src/storage/in_memory.rs: simple
(unqualified) limits live in a plain map keyed by limit identity; qualified
counters live in an LRU cache bounded by ``cache_size``
(in_memory.rs:13-16,204-212). ``check_and_update`` is
check-all-then-update-all and never over-admits (in_memory.rs:72-156).

Every other backend — including the TPU one — is tested for behavioral parity
against this implementation.
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict
from typing import Dict, List, Optional, Set

from ..core.counter import Counter
from ..core.limit import Limit
from .base import Authorization, CounterStorage
from .expiring_value import ExpiringValue
from .gcra import cell_for_limit as _new_cell

__all__ = ["InMemoryStorage"]

DEFAULT_CACHE_SIZE = 10_000


class InMemoryStorage(CounterStorage):
    supports_token_bucket = True

    def __init__(self, cache_size: int = DEFAULT_CACHE_SIZE, clock=time.time):
        self._lock = threading.RLock()
        self._clock = clock
        self._cache_size = int(cache_size)
        # limit identity -> window cell for unqualified limits
        self._simple: Dict[Limit, ExpiringValue] = {}
        # counter -> window cell, LRU-bounded, for qualified counters
        self._qualified: "OrderedDict[Counter, ExpiringValue]" = OrderedDict()

    # -- internals ---------------------------------------------------------

    def _qualified_get(self, counter: Counter) -> Optional[ExpiringValue]:
        ev = self._qualified.get(counter)
        if ev is not None:
            self._qualified.move_to_end(counter)
        return ev

    def _qualified_get_or_create(self, counter: Counter, now: float) -> ExpiringValue:
        ev = self._qualified_get(counter)
        if ev is None:
            # Created with value 0 and a fresh window, even on a pure check
            # (in_memory.rs:122-127).
            ev = _new_cell(counter.limit, now, fresh_window=True)
            self._qualified[counter.key()] = ev
            while len(self._qualified) > self._cache_size:
                self._qualified.popitem(last=False)
        return ev

    # -- CounterStorage ----------------------------------------------------

    def is_within_limits(self, counter: Counter, delta: int) -> bool:
        now = self._clock()
        with self._lock:
            if counter.is_qualified():
                ev = self._qualified_get(counter)
                value = ev.value_at(now) if ev is not None else 0
            else:
                ev = self._simple.get(counter.limit)
                value = ev.value_at(now) if ev is not None else 0
        return value + delta <= counter.max_value

    def _simple_get_or_create(self, limit: Limit) -> ExpiringValue:
        # NOT setdefault(limit, _new_cell(limit)): that constructed (and
        # discarded) a fresh cell on every call — the single largest
        # allocation churn of the oracle hot path.
        ev = self._simple.get(limit)
        if ev is None:
            ev = _new_cell(limit)
            self._simple[limit] = ev
        return ev

    def add_counter(self, limit: Limit) -> None:
        if not limit.variables:
            with self._lock:
                self._simple_get_or_create(limit)

    def update_counter(self, counter: Counter, delta: int) -> None:
        now = self._clock()
        with self._lock:
            if counter.is_qualified():
                ev = self._qualified_get_or_create(counter, now)
            else:
                ev = self._simple_get_or_create(counter.limit)
            ev.update(delta, counter.window_seconds, now)

    def check_and_update(
        self, counters: List[Counter], delta: int, load_counters: bool
    ) -> Authorization:
        now = self._clock()
        with self._lock:
            first_limited: Optional[Authorization] = None
            to_update: List[tuple] = []

            # Simple counters first, then qualified — same processing (and
            # first_limited) order as the reference (in_memory.rs:104-139).
            # One inlined loop body per pass: the per-counter closure call
            # and redundant cell construction profiled as ~40% of the
            # oracle's check path (the admission-breaker fallback lane,
            # which must not itself be the bottleneck).
            for qualified_pass in (False, True):
                for counter in counters:
                    if counter.is_qualified() is not qualified_pass:
                        continue
                    if qualified_pass:
                        ev = self._qualified_get_or_create(counter, now)
                    else:
                        ev = self._simple_get_or_create(counter.limit)
                    value = ev.value_at(now)
                    over = value + delta > counter.max_value
                    if load_counters:
                        remaining = counter.max_value - (value + delta)
                        counter.remaining = max(remaining, 0)
                        counter.expires_in = ev.ttl(now)
                        if first_limited is None and remaining < 0:
                            first_limited = Authorization.limited_by(
                                counter.limit.name
                            )
                    elif over:
                        return Authorization.limited_by(counter.limit.name)
                    to_update.append((ev, counter.window_seconds))

            if first_limited is not None:
                return first_limited

            for ev, window in to_update:
                ev.update(delta, window, now)
            return Authorization.OK

    def get_counters(self, limits: Set[Limit]) -> Set[Counter]:
        now = self._clock()
        out: Set[Counter] = set()
        with self._lock:
            namespaces = {limit.namespace for limit in limits}
            for limit, ev in self._simple.items():
                if limit.namespace in namespaces:
                    c = Counter(limit, {})
                    c.remaining = limit.max_value - ev.value_at(now)
                    c.expires_in = ev.ttl(now)
                    if c.expires_in > 0:
                        out.add(c)
            for counter, ev in self._qualified.items():
                if counter.limit in limits or counter.namespace in namespaces:
                    c = counter.key()
                    c.remaining = c.max_value - ev.value_at(now)
                    c.expires_in = ev.ttl(now)
                    if c.expires_in > 0:
                        out.add(c)
        return out

    def delete_counters(self, limits: Set[Limit]) -> None:
        with self._lock:
            for limit in limits:
                if not limit.variables:
                    self._simple.pop(limit, None)
                else:
                    for counter in [
                        c for c in self._qualified if c.limit == limit
                    ]:
                        del self._qualified[counter]

    def drop_counter(self, counter: Counter) -> bool:
        """Forget ONE counter's window cell (elastic pod, ISSUE 15): a
        migrated slice releases its cells on the old owner once the new
        owner acknowledged the copy — per-key, unlike
        ``delete_counters`` which drops a whole limit. Returns whether
        a cell existed."""
        with self._lock:
            if counter.is_qualified():
                return self._qualified.pop(counter.key(), None) is not None
            return self._simple.pop(counter.limit, None) is not None

    def clear(self) -> None:
        with self._lock:
            self._simple.clear()
            self._qualified.clear()

    def apply_deltas(self, items):
        """Authority-side batch apply for write-behind caches: apply each
        delta, return (post-apply value, ttl seconds) — the role the
        BATCH_UPDATE_COUNTERS Lua script plays for the reference
        (redis/scripts.rs:28-45)."""
        now = self._clock()
        out = []
        with self._lock:
            for counter, delta in items:
                if counter.is_qualified():
                    ev = self._qualified_get_or_create(counter, now)
                else:
                    ev = self._simple_get_or_create(counter.limit)
                value = ev.update(delta, counter.window_seconds, now)
                out.append((value, ev.ttl(now)))
        return out
