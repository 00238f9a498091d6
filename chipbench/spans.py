"""Host spans and counters, recorded by the harness around its calls into
each layer of the program.

Each span is (name, start, end) on ``time.perf_counter``.  With tracing on,
the same span is also a ``jax.profiler.TraceAnnotation`` named
``cb.<name>``, so the trace reduction finds it on the profiler's clock and
can say what the host was doing while the device sat idle.
"""
from __future__ import annotations

import contextlib
import time
from collections import Counter, defaultdict

import jax

PREFIX = "cb."


class Spans:
    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.spans = defaultdict(list)      # name -> [(t0, t1)]
        self.counters = Counter()
        self.samples = defaultdict(list)    # name -> [value], e.g. lengths

    @contextlib.contextmanager
    def span(self, name: str):
        ann = (jax.profiler.TraceAnnotation(PREFIX + name) if self.annotate
               else contextlib.nullcontext())
        t0 = time.perf_counter()
        with ann:
            try:
                yield
            finally:
                self.spans[name].append((t0, time.perf_counter()))

    def durations(self, name: str) -> list:
        return [t1 - t0 for t0, t1 in self.spans.get(name, [])]

    def reset(self) -> None:
        self.spans.clear()
        self.counters.clear()
        self.samples.clear()
