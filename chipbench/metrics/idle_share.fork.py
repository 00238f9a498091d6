"""Device idle share of the traced fork-per-invocation window, in %."""
from chipbench import readers


def read(rec):
    return readers.idle_pct(rec)
