"""Serving engine decode (_decode_batch, kv_cache.py): mean time of one
decode step over the active batch, from the harness's span."""
from chipbench import readers


def read(rec):
    return readers.mean_ms(rec, "decode")
