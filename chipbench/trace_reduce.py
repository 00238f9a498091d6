"""A profiler trace (``.xplane.pb``) reduced to what the metrics read.

* Device planes (``/device:TPU:<n>``): busy intervals are the union of the
  events on the ``XLA Ops`` line; per-program device time is the sum of the
  ``XLA Modules`` events by module name (``jit_paged_attention``, ...),
  with the trailing ``(<id>)`` dropped.
* Host planes: the harness's spans, written as ``TraceAnnotation``s named
  ``cb.<name>`` (``spans.py``).  The ``cb.window`` span bounds the window.
* Each idle gap on a device inside the window is put down to the innermost
  other harness span that covers its midpoint, ``(none)`` where none does;
  each program's device time likewise to the innermost span the host was
  in when the program started (``span_modules``).

``busy_s`` is averaged over the devices that ran anything.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict

PREFIX = "cb."
WINDOW = PREFIX + "window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_ID = re.compile(r"\(\d+\)$")
NONE = "(none)"


def module_name(name: str) -> str:
    return _ID.sub("", name).strip()


def _union(iv):
    """Sorted, merged intervals of ``iv`` [(start, end)]."""
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def read_planes(path):
    """(device planes {name: {"ops": [(s, e)], "modules": [(name, s, e)]}},
    host spans [(name, s, e)]), times in ns."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            d = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                if line.name == OPS_LINE:
                    d["ops"] += [(e.start_ns, e.end_ns) for e in line.events]
                elif line.name == MODULES_LINE:
                    d["modules"] += [(module_name(e.name), e.start_ns, e.end_ns)
                                     for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name[len(PREFIX):], e.start_ns, e.end_ns)
                         for e in line.events if e.name.startswith(PREFIX)]
    return devices, host


def reduce(path, top: int = 10) -> dict:
    devices, host = read_planes(path)
    wins = [(s, e) for n, s, e in host if n == "window"]
    spans = [(n, s, e) for n, s, e in host if n != "window"]
    if wins:
        lo, hi = min(s for s, _ in wins), max(e for _, e in wins)
    else:
        ends = [x for d in devices.values() for iv in d["ops"] for x in iv]
        lo, hi = (min(ends), max(ends)) if ends else (0.0, 0.0)
    busy, modules, idle = [], defaultdict(float), defaultdict(float)
    span_modules = defaultdict(lambda: defaultdict(float))
    bounds, labels = _segments(spans)

    def label(t):
        i = bisect.bisect_right(bounds, t) - 1
        return labels[i] if i >= 0 else NONE
    for d in devices.values():
        merged = _union(_clip(d["ops"], lo, hi))
        if not merged:
            continue
        busy.append(sum(e - s for s, e in merged))
        for name, s, e in d["modules"]:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                modules[name] += (e - s) / 1e9
                span_modules[label(s)][name] += (e - s) / 1e9
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                idle[label((s + e) / 2)] += (e - s) / 1e9
    n = max(1, len(busy))
    ranked = lambda d: [[k, v] for k, v in sorted(d.items(),  # noqa: E731
                                                  key=lambda kv: -kv[1])[:top]]
    return {"busy_s": sum(busy) / 1e9 / n, "window_s": (hi - lo) / 1e9,
            "devices": len(busy), "modules": dict(modules),
            "span_modules": {k: dict(v) for k, v in span_modules.items()},
            "device_ops": ranked({k: v / n for k, v in modules.items()}),
            "idle_gaps": ranked({k: v / n for k, v in idle.items()})}


def _segments(spans):
    """The harness's spans nest (one thread, context managers): the
    timeline cut where the innermost span changes, as (bounds, labels) —
    from ``bounds[i]`` on, the host was in ``labels[i]``."""
    bounds, labels, stack = [], [], []

    def pop():
        _, _, end = stack.pop()
        bounds.append(end)
        labels.append(stack[-1][0] if stack else NONE)

    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= s:
            pop()
        stack.append((name, s, e))
        bounds.append(s)
        labels.append(name)
    while stack:
        pop()
    return bounds, labels
