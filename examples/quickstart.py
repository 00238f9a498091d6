"""Quickstart: the MITOSIS-JAX remote fork in 60 lines.

Builds a 2-node cluster, deploys one seed LM replica, remote-forks it to the
second node (descriptor-only transfer + on-demand paging), and generates
text on the child — verifying it matches the parent exactly.

  PYTHONPATH=src python examples/quickstart.py
"""
import time

import jax
import jax.numpy as jnp

from repro.configs.base import get_arch
from repro.core.instance import ModelInstance
from repro.net import Network
from repro.fork import ForkPolicy
from repro.models import lm
from repro.platform.node import NodeRuntime
from repro.serving.engine import ServingEngine


def main():
    cfg = get_arch("micro-small")
    net = Network()
    parent_node = NodeRuntime("parent", net)
    child_node = NodeRuntime("child", net)

    # 1. one seed replica — the only provisioned instance in the cluster
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    seed = ModelInstance.create(parent_node, cfg.name, params)
    handle = parent_node.prepare_fork(seed)
    print(f"seed: {seed.total_bytes()/2**20:.1f} MiB state, descriptor = "
          f"{len(parent_node.seeds[handle.handler_id].blob)} bytes")

    # 2. remote fork: child maps the parent's pages, fetches on demand
    t0 = time.perf_counter()
    child = handle.resume_on(child_node, ForkPolicy(lazy=True, prefetch=1))
    print(f"resume_on: {(time.perf_counter()-t0)*1e3:.1f} ms "
          f"(resident: {child.resident_fraction():.0%})")

    child_params = child.materialize_pytree()
    print(f"materialized on demand: {child.stats['pages_rdma']} pages over "
          f"RDMA, {net.meter['rdma_bytes']/2**20:.1f} MiB")

    # 3. serve from the child; parent and child agree bit-for-bit
    prompt = [11, 42, 7, 300]
    out = {}
    for tag, p in (("parent", params), ("child", child_params)):
        eng = ServingEngine(cfg, p, backend="auto")
        rid = eng.submit(prompt, max_tokens=8)
        out[tag] = eng.run_to_completion()[rid]
        print(f"{tag} generated: {out[tag]}")
    assert out["parent"] == out["child"]
    print("child == parent: OK")


if __name__ == "__main__":
    main()
