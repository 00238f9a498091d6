#!/usr/bin/env python3
"""Find a cell's knee: one set-up, then windows at rising offered rates.

  python3 chipbench/sweep.py --workload <cell> --seed <n> --seconds <s> \
      [--fractions 0.5,0.7,0.8,0.9,1.0,1.2]

First a burst (every request due at once) measures the capacity, in
requests per second completed.  Then each window offers a fraction of it,
from a fresh seed, and prints the latency the cell reports (median, 95th
percentile, count), the rate completed and how late the last request
finished.  The knee is the highest rate whose tail stays flat; the cell's
traffic file then gets 0.8 of it as a number.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--fractions", default="0.5,0.7,0.8,0.9,1.0,1.2")
    ap.add_argument("--burst", type=int, default=12)
    args = ap.parse_args(argv)
    import jax

    from chipbench import run, spec
    from chipbench.spans import Spans
    from chipbench.traffic import generate
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if jax.devices()[0].platform != "tpu":
        run.log("sweep needs a TPU")
        return 2
    bench = spec.load_benchmark()
    wl = spec.workload(bench, args.workload)
    conf = spec.load_config(bench, wl["config"])
    mix = spec.load_traffic(wl["traffic"])
    sp = Spans()
    drv = spec.driver_module(mix["kind"]).Driver(conf, mix, args.seed, sp,
                                                  run.log)
    drv.setup()
    vocab = drv.model.dims.vocab

    def window(rate, seconds, seed, burst=False):
        sched = generate.schedule(mix, seed, seconds, vocab, rate=rate)
        if burst:
            for r in sched:
                r.due = 0.0
        t0 = time.perf_counter()
        recs = drv.window(sched, seconds, t0)
        span = time.perf_counter() - t0
        lat = run.latencies(recs)
        return {"offered_per_s": rate, "requests": len(recs),
                "failed": sum(r["failed"] for r in recs),
                "completed_per_s": sum(not r["failed"] for r in recs) / span,
                "span_s": span,
                **{f"{k}_ms_p50": run.percentile(v, 50) for k, v in lat.items()},
                **{f"{k}_ms_p95": run.percentile(v, 95) for k, v in lat.items()}}

    b = window(args.burst / 1.0, 1.0, args.seed + 1000, burst=True)
    cap = b["requests"] / b["span_s"]
    print(json.dumps({"burst": b, "capacity_per_s": cap}), flush=True)
    for i, f in enumerate(float(x) for x in args.fractions.split(",")):
        row = window(f * cap, args.seconds, args.seed + 1 + i)
        print(json.dumps({"fraction": f, **row}), flush=True)
    drv.release()
    return 0


if __name__ == "__main__":
    sys.exit(main())
