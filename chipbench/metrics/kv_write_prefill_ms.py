"""KV cache (serving/kv_cache.py): mean time of PagedKV.write_prefill, the
per-column copy of a prefill's K/V into the page pool, over the prefills in
the window (the program's kv.write_prefill spans)."""
from chipbench import program


def read(rec):
    return program.mean_ms(rec, "kv.write_prefill")
