"""The program's own spans and counters (``repro.tracing``) inside the
harness's window.

The window is the harness's ``window`` span, on the same
``time.perf_counter`` clock as the program's records.  Where there is
nothing to read, these return None: a program without the recorder, a run
without a window, or a ring that dropped records from inside the window."""
from __future__ import annotations


def window_records(rec):
    """The program's records inside the window, or None."""
    try:
        from repro import tracing
    except ImportError:
        return None
    win = rec.spans.spans.get("window")
    if not win:
        return None
    t0, t1 = win[-1]
    return tracing.TRACER.window(t0, t1)


def mean_ms(rec, name: str):
    """Mean duration of the program's ``name`` records in the window."""
    recs = window_records(rec)
    d = [r.t1 - r.t0 for r in recs or () if r.name == name]
    return 1e3 * sum(d) / len(d) if d else None


def counts(records, keys) -> float:
    """Sum of the counters ``keys`` over ``records``' self counts."""
    return sum((r.counts or {}).get(k, 0) for r in records for k in keys)


def under(records, name: str) -> dict:
    """{id of each ``name`` record: [it and every record nested in it]}."""
    roots = {r.id: [r] for r in records if r.name == name}
    parent = {r.id: r.parent for r in records}
    for r in records:
        p = r.parent
        while p is not None and p not in roots:
            p = parent.get(p)
        if p is not None:
            roots[p].append(r)
    return roots
