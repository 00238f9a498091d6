"""Fault handler and page pool (core/instance.py, memory/pool.py): mean
time of materialize_pytree to block_until_ready per invocation, from the
harness's span."""
from chipbench import readers


def read(rec):
    return readers.mean_ms(rec, "materialize")
