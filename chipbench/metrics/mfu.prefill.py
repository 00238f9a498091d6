"""Prefill's share of the chip's bf16 peak: required prefill FLOPs over
(prefill span time x peak)."""
from chipbench import readers


def read(rec):
    return readers.flops_pct(rec, "prefill", "prefill_flops")
