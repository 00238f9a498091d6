#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

  python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up makes the cell's weights from the seed, seeds them in a parent
node's host pool, forks to a device-pool child node and warms every shape
the cell's traffic can draw.  The window then serves the seeded schedule
for ``--seconds``; requests due in it are drained after it.  ``--trace 0``
reports the cell's end-to-end metrics, ``--trace 1`` traces the window and
reports its per-layer metrics.  Afterwards the program's state is freed and
the plain reference decides ``correct`` (``check.py``).

The last line of standard output is one JSON object; the numbers compared
and their limits are also the last lines of standard error.  Without a TPU,
or with fewer chips than the cell asks for, it exits non-zero and prints no
result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from collections import Counter  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

TRACE_DIR = ROOT / ".chipbench" / "trace"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def percentile(samples, q: float):
    """Linear interpolation between order statistics (as
    ``repro.sim.metrics.percentile``); None without samples."""
    import numpy as np
    return float(np.percentile(np.asarray(samples, float), q)) if len(samples) \
        else None


def latencies(recs: list) -> dict:
    """Host-clock latencies (ms) of the finished requests, from their due
    times: whole request, first token, and every gap between tokens."""
    done = [r for r in recs if not r["failed"]]
    return {"last": [1e3 * (r["times"][-1] - r["due"]) for r in done],
            "first": [1e3 * (r["times"][0] - r["due"]) for r in done],
            "gaps": [1e3 * (b - a) for r in done
                     for a, b in zip(r["times"], r["times"][1:])]}


def end_to_end(recs: list, setup_s: float, peak_bytes: int) -> dict:
    lat = latencies(recs)
    return {"invoke_ms_p95": percentile(lat["last"], 95),
            "ttft_ms_p95": percentile(lat["first"], 95),
            "itl_ms_p95": percentile(lat["gaps"], 95),
            "hbm_peak_gib": peak_bytes / 2**30,
            "setup_s": setup_s}


def run_cell(bench: dict, wl: dict, seed: int, seconds: float, trace: bool,
             *, conf=None, mix=None, limits=None, device_kind=None,
             t_start: float = T_START, control: bool = False) -> dict:
    """Set up, warm, run the window, read the metrics, free the program's
    state and decide ``correct``.  ``conf``, ``mix`` and ``limits`` default
    to the cell's files.  ``control`` puts the fp8 control in the program's
    place for the served tokens: ``correct`` is then the control's verdict
    under the cell's limits, and ``readings`` holds the program's gap
    beside it."""
    import jax

    from chipbench import check, spec
    from chipbench import trace_reduce, work
    from chipbench.spans import Spans
    from chipbench.traffic import generate

    conf = conf or spec.load_config(bench, wl["config"])
    mix = mix or spec.load_traffic(wl["traffic"])
    limits = limits or spec.load_limits(wl["name"])
    dev = jax.devices()[0]
    kind = device_kind or dev.device_kind
    sp = Spans(annotate=trace)
    drv = spec.driver_module(mix["kind"]).Driver(conf, mix, seed, sp, log)
    drv.setup()
    sched = generate.schedule(mix, seed, seconds, drv.dm.vocab)
    setup_split = {k: sum(sp.durations(k)) for k in sp.spans}
    sp.reset()
    compiles = Counter()
    jax.monitoring.register_event_listener(
        lambda ev, **kw: compiles.update([ev.rsplit("/", 1)[-1]]))
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, secs, **kw: compiles.update(
            {"compile_s": secs} if ev == COMPILE_EVENT else {}))
    setup_s = time.perf_counter() - t_start
    setup_peak = int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))

    tdir = TRACE_DIR / f"{wl['name']}-{seed}"
    if trace:
        shutil.rmtree(tdir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # harness spans, not every call
        jax.profiler.start_trace(str(tdir), profiler_options=opts)
    with sp.span("window"):
        t0 = time.perf_counter()
        recs = drv.window(sched, seconds, t0)
        window_s = time.perf_counter() - t0
    if trace:
        jax.profiler.stop_trace()
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    in_window = {k: compiles[k] for k in ("cache_misses", "cache_hits",
                                          "compile_s")}
    failed = sum(r["failed"] for r in recs)
    log(f"setup {setup_s:.3f} s {json.dumps(setup_split)}; window "
        f"{window_s:.3f} s; {len(recs)} requests, {failed} failed; "
        f"programs compiled or loaded in the window: {in_window}; "
        f"peak {peak} B (at the end of set-up {setup_peak} B)")

    out = {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    if trace:
        red = trace_reduce.reduce(next(tdir.rglob("*.xplane.pb")))
        shutil.rmtree(tdir, ignore_errors=True)
        rec = types.SimpleNamespace(spans=sp, work=drv.work(), trace=red,
                                    peaks=work.peaks(kind), dims=drv.dm,
                                    window_s=window_s)
        for m in spec.cell_metrics(bench, wl["name"], trace=True):
            v = spec.metric_reader(m["name"]).read(rec)
            if v is not None:
                out[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
    else:
        e2e = end_to_end(recs, setup_s, peak)
        for m in spec.cell_metrics(bench, wl["name"], trace=False):
            if e2e.get(m["name"]) is not None:
                out[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    lat = latencies(recs)
    log("latency ms p50/p95 (n): " + ", ".join(
        f"{k} {percentile(v, 50)}/{percentile(v, 95)} ({len(v)})"
        for k, v in lat.items()))

    sums = getattr(drv, "sums", None)
    if sums is not None:
        sums = {k: jax.device_get(v) for k, v in sums.items()}
    cfg, dm = drv.cfg, drv.dm
    drv.release()
    del drv
    gc.collect()
    t_ref = time.perf_counter()
    picked = check.sample(recs, int(mix["sample_requests"]), seed)
    read = check.readings(cfg, dm, seed, picked, sums=sums, control=control)
    read["failed"] = failed
    correct, checks = check.verdict(read, {**limits, "failed": 0}, failed)
    if control:
        log(f"control in the program's place; readings {json.dumps(read)}")
    log(f"reference over {len(picked)} requests, "
        f"{read['served_tokens_compared']} tokens: "
        f"{time.perf_counter() - t_ref:.3f} s")
    result = {"correct": bool(correct and picked), "attempted": len(recs),
              "failed": failed, "metrics": out, "device": device}
    if trace:
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    if control:
        result["readings"] = read
    result["checks"] = checks
    for k, c in checks.items():
        log(f"check {k} {c['value']} limit {c['limit']}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="judge the fp8 control in the program's place, "
                         "with the program's gap beside it (to set limits; "
                         "the benchmark's runs leave it off)")
    args = ap.parse_args(argv)
    try:
        from chipbench import spec
        from repro.launch.compile_cache import enable_compile_cache
        bench = spec.load_benchmark()
        wl = spec.workload(bench, args.workload)
    except (ImportError, OSError, KeyError) as e:
        log(f"cannot load the cell or the program: {e!r}")
        return 2
    import jax
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < int(wl["chips"]):
        log(f"needs {wl['chips']} TPU chip(s); JAX found {len(devs)} "
            f"{devs[0].platform} device(s)")
        return 2
    result = run_cell(bench, wl, args.seed, args.seconds, bool(args.trace),
                      control=bool(args.control))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
