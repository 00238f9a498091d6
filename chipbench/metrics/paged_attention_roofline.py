"""paged_attention's share of its roofline: the K and V pages each
sequence's attended tokens cover, plus q and out (HBM bound; flops
4 x H x hd x length), over the device time of jit_paged_attention."""
from chipbench import readers


def read(rec):
    return readers.roofline_pct(rec, r"^jit_paged_attention$", "paged_attention")
