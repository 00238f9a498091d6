"""Drive whole runs of the tiny cells on the CPU, past the harness's look
for a chip."""
from __future__ import annotations

import time
import warnings

from chipbench import run, spec
from chipbench.tests import tiny

SEED = 2**31 + 977


def run_tiny(kind: str, *, seed: int = SEED, control: bool = False,
             seconds: float = 1.0, **mix_kw) -> dict:
    mix = {**(tiny.FORK_MIX if kind == "fork" else tiny.SERVE_MIX), **mix_kw}
    limits = dict(tiny.LIMITS)
    if kind == "fork":
        limits["child_params_differ"] = 0
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        return run.run_cell(tiny.bench(kind), tiny.workload(kind), seed,
                            seconds, False, conf=tiny.TINY_CONF, mix=mix,
                            limits=limits, device_kind="TPU v5 lite",
                            t_start=time.perf_counter(), control=control)
