"""Per-layer readers: a reader with nothing to read returns None, and a
share computed from required work stays a share."""
import types

import pytest

from chipbench import readers, spec, work
from chipbench.spans import Spans


def _rec(**kw):
    base = dict(spans=Spans(), work={}, peaks=work.peaks("TPU v5 lite"),
                trace={"modules": {}, "busy_s": 0.0, "window_s": 0.0},
                dims=None, window_s=0.0)
    return types.SimpleNamespace(**{**base, **kw})


READERS = sorted(p.stem for p in (spec.HERE / "metrics").glob("*.py"))


@pytest.mark.parametrize("name", READERS)
def test_nothing_to_read_is_none(name):
    """Every reader, also those of cells still to be added."""
    assert spec.metric_reader(name).read(_rec()) is None


def test_kernel_roofline():
    # 819 MB moved in 2 ms is half the HBM roofline;
    # page_gather counts what ran in materialize spans except cow_scatter:
    # the gather kernels and the epilogue that lands pages as tensors, not
    # a reshape the serving engine ran elsewhere
    rec = _rec(trace={"modules": {"jit_page_gather": 1e-3,
                                  "jit_page_gather_runs": 5e-4,
                                  "jit_reshape": 9e-3,
                                  "jit_cow_scatter_runs": 4e-3},
                      "span_modules": {
                          "materialize": {"jit_page_gather": 1e-3,
                                          "jit_page_gather_runs": 5e-4,
                                          "jit_reshape": 5e-4,
                                          "jit_cow_scatter_runs": 4e-3},
                          "serve": {"jit_reshape": 8.5e-3}},
                      "busy_s": 0.5, "window_s": 2.0},
               work={"page_gather": (0, 819e6), "cow_scatter": (0, 819e6)})
    pg = spec.metric_reader("page_gather_roofline").read(rec)
    cs = spec.metric_reader("cow_scatter_roofline").read(rec)
    assert pg == pytest.approx(50.0) and cs == pytest.approx(25.0)
    assert spec.metric_reader("idle_share.fork").read(rec) == pytest.approx(75.0)


def test_span_means_and_flops_share():
    sp = Spans()
    sp.spans["prefill"] = [(0.0, 0.1), (1.0, 1.3)]
    rec = _rec(spans=sp, work={"prefill_flops": 197e12 * 0.04})
    assert readers.mean_ms(rec, "prefill") == pytest.approx(200.0)
    assert spec.metric_reader("mfu.prefill").read(rec) == pytest.approx(10.0)
