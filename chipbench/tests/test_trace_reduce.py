"""The trace reduction: interval arithmetic, and a small trace recorded on
a TPU v5e (two rounds of page_gather, page_gather_runs, cow_scatter_runs
and paged_attention under ``cb.step`` spans, with ``cb.wait`` sleeps
between them, all inside ``cb.window``)."""
from pathlib import Path

import pytest

from chipbench import trace_reduce as tr

FIXTURE = Path(__file__).parent / "data" / "v5e_small.xplane.pb"


def test_union_and_clip():
    assert tr._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]
    assert tr._clip([(0, 2), (3, 9)], 1, 5) == [(1, 2), (3, 5)]


def test_nested_spans_give_the_innermost():
    bounds, labels = tr._segments([("step", 0, 10), ("decode", 2, 4),
                                   ("wait", 12, 15)])
    at = lambda t: labels[max(i for i, b in enumerate(bounds) if b <= t)]  # noqa: E731
    assert [at(t) for t in (1, 3, 5, 11, 13, 16)] == \
        ["step", "decode", "step", tr.NONE, "wait", tr.NONE]


def test_module_names_drop_their_id():
    assert tr.module_name("jit_paged_attention(12)") == "jit_paged_attention"


def test_recorded_v5e_trace():
    red = tr.reduce(FIXTURE)
    assert red["devices"] == 1
    assert 0 < red["busy_s"] < red["window_s"]
    mods = red["modules"]
    for name in ("jit_page_gather", "jit_page_gather_runs",
                 "jit_cow_scatter_runs", "jit_paged_attention"):
        assert mods.get(name, 0) > 0, name
    # each program's time goes to the span the host was in when it started:
    # all of it somewhere, and a step's kernels under it (the recorded steps
    # did not wait for their last program, which started during the sleep)
    by_span = red["span_modules"]
    assert set(by_span) == {"step", "wait"}
    assert sum(sum(m.values()) for m in by_span.values()) == \
        pytest.approx(sum(mods.values()), rel=1e-9)
    assert by_span["step"]["jit_cow_scatter_runs"] == mods["jit_cow_scatter_runs"]
    idle = dict(red["idle_gaps"])
    assert idle.get("wait", 0) > idle.get("step", 0)
    assert sum(idle.values()) == pytest.approx(red["window_s"] - red["busy_s"],
                                               rel=1e-6)
    assert len(red["device_ops"]) <= 10
