"""KV cache and page pool (serving/kv_cache.py, memory/pool.py): MiB copied
between host and device by the page pool in one decode step, both ways,
counted where each copy happens and summed over each engine.decode span and
the spans nested in it, over the number of decode steps in the window."""
from chipbench import program

KEYS = ("pool.h2d_bytes", "pool.d2h_bytes")


def read(rec):
    recs = program.window_records(rec)
    steps = program.under(recs or [], "engine.decode")
    if not steps:
        return None
    total = sum(program.counts(r, KEYS) for r in steps.values())
    return total / len(steps) / 2**20
