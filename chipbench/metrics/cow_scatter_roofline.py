"""cow_scatter's share of its roofline: 2 x pages x page bytes (HBM bound)
over the device time of jit_cow_scatter and jit_cow_scatter_runs."""
from chipbench import readers


def read(rec):
    return readers.roofline_pct(rec, r"^jit_cow_scatter(_runs)?$", "cow_scatter")
