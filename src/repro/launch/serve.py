"""Serving driver: spin up a mini cluster, deploy a seed, serve requests via
remote fork, demo KV-prefix forking.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch micro-small --requests 4
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs.base import get_arch
from repro.core.instance import ModelInstance
from repro.net import Network
from repro.fork import ForkPolicy
from repro.launch.compile_cache import enable_compile_cache
from repro.models import lm
from repro.platform.node import NodeRuntime
from repro.serving.engine import ServingEngine


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="micro-small")
    ap.add_argument("--nodes", type=int, default=3)
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--max-tokens", type=int, default=8)
    ap.add_argument("--fork-demo", action="store_true")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_arch(args.arch)
    net = Network()
    nodes = [NodeRuntime(f"node{i}", net, cache_enabled=True)
             for i in range(args.nodes)]

    # Seed replica on node0 — the single provisioned instance (O(1))
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    seed_inst = ModelInstance.create(nodes[0], cfg.name, params)
    handle = nodes[0].prepare_fork(seed_inst)
    print(f"[serve] seed on node0: {seed_inst.total_bytes()/2**20:.1f} MiB, "
          f"descriptor {len(nodes[0].seeds[handle.handler_id].blob)/1024:.1f} KiB")

    # Scale out: each remaining node forks the seed and serves
    policy = ForkPolicy(lazy=True, prefetch=1)
    engines = []
    for node in nodes[1:]:
        t0 = time.perf_counter()
        child = handle.resume_on(node, policy)
        child_params = child.materialize_pytree()
        dt = time.perf_counter() - t0
        print(f"[serve] {node.node_id}: forked replica in {dt*1e3:.1f} ms "
              f"({child.stats['pages_rdma']} pages via RDMA)")
        engines.append(ServingEngine(cfg, child_params, backend="auto"))

    rng = jax.random.PRNGKey(1)
    for i in range(args.requests):
        eng = engines[i % len(engines)]
        prompt = jax.random.randint(jax.random.fold_in(rng, i), (6,), 0,
                                    cfg.vocab_size).tolist()
        rid = eng.submit(prompt, max_tokens=args.max_tokens)
        out = eng.run_to_completion()[rid]
        print(f"[serve] req{i} -> {out}")

    if args.fork_demo:
        eng = engines[0]
        r0 = eng.submit([1, 2, 3, 4], max_tokens=6)
        eng.step()
        eng.step()      # prefill + two decode steps, request still live
        kids = [eng.fork_request(r0, max_tokens=4) for _ in range(3)]
        res = eng.run_to_completion()
        print(f"[serve] fork-demo parent={res[r0]} children="
              f"{[res[k] for k in kids]} (shared prefix pages, COW)")
    print("[serve] network:", net.snapshot())


if __name__ == "__main__":
    main()
