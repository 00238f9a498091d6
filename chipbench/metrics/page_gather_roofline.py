"""page_gather's share of its roofline, with gather_assemble's epilogue:
2 x pages x page bytes (HBM bound: each page read once, each tensor written
once) over the device time of every program started inside the harness's
materialize spans except cow_scatter's.  That is jit_page_gather(_runs)
and the reshape, trim and cast that land its pages as tensors, which a
fused epilogue would shorten."""
from chipbench import readers


def read(rec):
    return readers.roofline_pct(rec, r"^(?!jit_cow_scatter)", "page_gather",
                                span="materialize")
