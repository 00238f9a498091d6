"""Serving engine admission (serving/engine.py): mean time a request waited
in the engine's queue, from submit to the start of its prefill, over the
requests admitted in the window (the program's engine.queue waits)."""
from chipbench import program


def read(rec):
    return program.mean_ms(rec, "engine.queue")
