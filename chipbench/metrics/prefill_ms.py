"""Serving engine prefill (serving/engine.py): mean time of one prefill,
from the harness's span around the engine's prefill entry, which ends
with its first token on the host."""
from chipbench import readers


def read(rec):
    return readers.mean_ms(rec, "prefill")
