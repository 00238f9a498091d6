#!/usr/bin/env python3
"""Compile each cell's jitted pieces for a described TPU v5e, without the
chip, and print what the compiler says they need.

  JAX_PLATFORMS=cpu python3 chipbench/rehearse.py [--workload <cell>]

Per cell: the weight maker, page_gather and cow_scatter over the largest
leaf's pages of a pool of the model's size, paged_attention at the batch and
pages the traffic can reach, the prefill of the longest prompt (jitted here,
an upper bound on what the eager engine holds at once) and the reference at
the longest sequence it checks.  A compile that passes is not a chip run.
"""
from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from chipbench import model, reference, spec  # noqa: E402
from repro.kernels.cow_scatter.kernel import cow_scatter_runs  # noqa: E402
from repro.kernels.page_gather.kernel import page_gather  # noqa: E402
from repro.kernels.paged_attention.kernel import paged_attention  # noqa: E402
from repro.models import lm  # noqa: E402

GIB = 2**30


def _report(what: str, fn, *args, **kw) -> None:
    m = jax.jit(fn, **kw).lower(*args).compile().memory_analysis()
    print(f"  {what}: args {m.argument_size_in_bytes / GIB:.3f} GiB, out "
          f"{m.output_size_in_bytes / GIB:.3f} GiB, temp "
          f"{m.temp_size_in_bytes / GIB:.3f} GiB", flush=True)


def rehearse(bench: dict, wl: dict, dev) -> None:
    conf = spec.load_config(bench, wl["config"])
    mix = spec.load_traffic(wl["traffic"])
    cfg, dm = model.arch(conf), model.dims(conf)
    one = SingleDeviceSharding(dev)
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one)  # noqa: E731
    npages = model.param_pages(cfg)
    print(f"{wl['name']}: {npages} pages of {model.PAGE_ELEMS} "
          f"{cfg.param_dtype}", flush=True)
    key = sds((2,), jnp.uint32)
    _report("weights", model.make_weights_fn(cfg, dm), key)
    shapes = jax.eval_shape(lambda k: lm.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    big = max(-(-x.size // model.PAGE_ELEMS) for x in jax.tree.leaves(shapes))
    dt = jnp.dtype(cfg.param_dtype)
    frames = sds((npages, model.PAGE_ELEMS // 128, 128), dt)
    ids = sds((big,), jnp.int32)
    _report(f"page_gather x{big}", lambda f, i: page_gather(
        f, i, interpret=False), frames, ids)
    runs = sds((1,), jnp.int32)
    _report(f"cow_scatter_runs x{big}", lambda f, s, n, o, p: cow_scatter_runs(
        f, s, n, o, p, max_len=big, interpret=False), frames, runs, runs,
        runs, sds((big, model.PAGE_ELEMS), dt), donate_argnums=(0,))
    vals = mix["prompt_tokens"]["values"]
    tp = int(mix.get("kv_page_tokens", 16))
    longest = max(vals) + int(mix["output_tokens"])
    batch = int(mix.get("max_active", 1))
    pages = -(-(longest + 1) // tp)
    ct = jnp.dtype(cfg.compute_dtype)
    G = dm.heads // dm.kv_heads
    kv = sds((batch * 2 * dm.layers * pages, dm.kv_heads, tp, dm.head_dim), ct)
    _report(f"paged_attention B={batch} P={pages}",
            lambda q, k, v, t, n: paged_attention(q, k, v, t, n,
                                                  interpret=False),
            sds((batch, dm.kv_heads, G, dm.head_dim), ct), kv, kv,
            sds((batch, pages), jnp.int32), sds((batch,), jnp.int32))
    params = jax.tree.map(lambda x: sds(x.shape, x.dtype), shapes)
    S = max(vals)
    _report(f"prefill S={S}", lambda p, t: lm.prefill(
        p, cfg, t, -(-S // tp) * tp), params, sds((1, S), jnp.int32))
    T = longest - 1
    _report(f"reference T={T}", lambda p, t: reference.logits_at(
        p, t, dm=dm, first=max(vals) - 1), params, sds((T,), jnp.int32))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", action="append")
    args = ap.parse_args(argv)
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    bench = spec.load_benchmark()
    for wl in bench["workloads"]:
        if not args.workload or wl["name"] in args.workload:
            rehearse(bench, wl, topo.devices[0])
    return 0


if __name__ == "__main__":
    sys.exit(main())
