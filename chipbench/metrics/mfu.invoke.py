"""Whole invocation's share of the chip's bf16 peak: the prefill and decode
FLOPs it requires over (invocation time x peak)."""
from chipbench import readers


def read(rec):
    return readers.flops_pct(rec, "invoke", "invoke_flops")
