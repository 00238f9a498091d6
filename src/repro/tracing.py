"""Spans and counters inside the program, on the profiler's clock.

One recorder per process (``TRACER``), always on.  A span is a context
manager::

    with tracing.span("engine.prefill", rid):
        ...

It records its name, start and end on ``time.perf_counter`` (the clock of
the benchmark's own spans), the span it opened inside, the request id or
ids it serves (inherited from the enclosing span where none is given), and
its self counts: what ``count`` added while it was the innermost open span.
Each span is also a ``jax.profiler.TraceAnnotation`` named
``repro.<name>``, so a profiler trace holds it on the host plane beside the
device's programs; with the profiler off that is one native enter and exit.
Spans nest as context managers on one thread.

Finished spans go to a bounded ring.  ``Tracer.window(t0, t1)`` returns the
records inside an interval, or None where the ring has dropped a record
that may lie inside it, so a reader never undercounts.

A wait (``wait``) is recorded with an explicit start and goes to the ring
only: it overlaps the host's work, and in the profiler's trace it would
break the nesting the trace reduction relies on.

Counters are charged to the innermost open span and to ``Tracer.totals``:
the page pool's copies across the host-device boundary (``pool.*``) and
compiles (``compile.*``), from ``jax.monitoring`` listeners registered once
on import.  ``compile.backend`` counts programs XLA compiled (and
``compile.backend_s`` their seconds), ``compile.cache_hits`` programs loaded
from the persistent compilation cache.
"""
from __future__ import annotations

import collections
import itertools
import math
import time
from typing import List, Optional

import jax
from jax.profiler import TraceAnnotation

PREFIX = "repro."
RING = 1 << 16
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class Span:
    """One span; once finished, its record in the ring."""
    __slots__ = ("name", "rid", "t0", "t1", "id", "parent", "counts",
                 "_tracer", "_ann")

    def __init__(self, tracer: "Tracer", name: str, rid=None):
        self._tracer = tracer
        self.name = name
        self.rid = rid
        self.parent = None
        self.counts = None          # {counter: n}, made on the first count

    def __enter__(self) -> "Span":
        tr = self._tracer
        stack = tr._stack
        if stack:
            top = stack[-1]
            self.parent = top.id
            if self.rid is None:
                self.rid = top.rid
        self.id = next(tr._ids)
        stack.append(self)
        self._ann = TraceAnnotation(PREFIX + self.name)
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        self._ann = None
        tr, self._tracer = self._tracer, None
        tr._stack.pop()
        tr._finish(self)


class Tracer:
    def __init__(self, capacity: int = RING):
        self.ring = collections.deque(maxlen=capacity)
        self.totals = collections.Counter()
        # end of the latest record the full ring has dropped
        self.dropped_until = -math.inf
        self._stack: List[Span] = []
        self._ids = itertools.count(1)
        self._hit_pending = False

    def span(self, name: str, rid=None) -> Span:
        return Span(self, name, rid)

    def wait(self, name: str, t0: float, rid=None) -> None:
        """A wait from ``t0`` until now: to the ring, not to the profiler."""
        rec = Span(self, name, rid)
        rec.id, rec.t0, rec.t1 = next(self._ids), t0, time.perf_counter()
        rec._tracer = None
        self._finish(rec)

    def count(self, name: str, n=1) -> None:
        self.totals[name] += n
        if self._stack:
            top = self._stack[-1]
            if top.counts is None:
                top.counts = {}
            top.counts[name] = top.counts.get(name, 0) + n

    def window(self, t0: float, t1: float) -> Optional[List[Span]]:
        """Records that start and end inside [t0, t1]; None if the ring has
        dropped any that ended at or after ``t0``."""
        if self.dropped_until >= t0:
            return None
        return [r for r in self.ring if r.t0 >= t0 and r.t1 <= t1]

    def _finish(self, rec: Span) -> None:
        ring = self.ring
        if len(ring) == ring.maxlen:
            self.dropped_until = max(self.dropped_until, ring[0].t1)
        ring.append(rec)

    # jax.monitoring: every compile request fires BACKEND_COMPILE when it
    # returns; one served by the persistent cache fires CACHE_HIT before it
    def _on_event(self, event: str) -> None:
        if event == CACHE_HIT:
            self._hit_pending = True
            self.count("compile.cache_hits")

    def _on_duration(self, event: str, secs: float) -> None:
        if event != BACKEND_COMPILE:
            return
        if self._hit_pending:
            self._hit_pending = False
        else:
            self.count("compile.backend")
            self.count("compile.backend_s", secs)


TRACER = Tracer()


def span(name: str, rid=None) -> Span:
    return TRACER.span(name, rid)


def wait(name: str, t0: float, rid=None) -> None:
    TRACER.wait(name, t0, rid)


def count(name: str, n=1) -> None:
    TRACER.count(name, n)


jax.monitoring.register_event_listener(
    lambda event, **kw: TRACER._on_event(event))
jax.monitoring.register_event_duration_secs_listener(
    lambda event, secs, **kw: TRACER._on_duration(event, secs))
