"""Model step compiles (models/lm.py, serving/engine.py): programs compiled
by XLA or loaded from the persistent compilation cache inside the program's
spans in the window (compile.backend + compile.cache_hits, from
jax.monitoring)."""
from chipbench import program


def read(rec):
    recs = program.window_records(rec)
    if not recs:
        return None
    return program.counts(recs, ("compile.backend", "compile.cache_hits"))
