"""Device idle share of the traced serving window, in %."""
from chipbench import readers


def read(rec):
    return readers.idle_pct(rec)
