"""Decode's share of the chip's bf16 peak: required decode FLOPs over
(decode span time x peak)."""
from chipbench import readers


def read(rec):
    return readers.flops_pct(rec, "decode", "decode_flops")
