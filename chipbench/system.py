"""The system under test, as both drivers set it up: the benchmark's
weights seeded in a parent node's host page pool, a fork handle to them, and
a child node whose page pool lives on the device (the kernel path:
page_gather, cow_scatter, paged_attention)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from chipbench import model
from repro.core.instance import ModelInstance
from repro.fork import ForkPolicy
from repro.net import Network
from repro.platform.node import NodeRuntime
from repro.serving.engine import ServingEngine
from repro.serving.kv_cache import PagedKV
from repro.memory.pool import PagePool


class System:
    def __init__(self, conf: dict, seed: int, spans, log):
        self.conf, self.seed, self.sp, self.log = conf, seed, spans, log
        self.dm = model.dims(conf)
        self.cfg = model.arch(conf)
        self.npages = model.param_pages(self.cfg)
        self.page_bytes = model.page_bytes(self.cfg)

    def seed_parent(self, on_device=None) -> None:
        """Weights from the seed, on the device in one call, then into the
        parent's host pool; the child node's device pool holds the whole
        model from the start, so it never grows.  ``on_device(weights)``
        runs while the weights are still on the device and no child exists
        (the serving shapes' warm-up), so what it holds stays below what a
        child and its serving hold in the window."""
        sp = self.sp
        make = model.make_weights_fn(self.cfg, self.dm)
        with sp.span("setup.weights"):
            w = jax.block_until_ready(make(model.seed_key(self.seed)))
        if on_device is not None:
            with sp.span("setup.warm_serve"):
                on_device(w)
        with sp.span("setup.to_host"):
            host = jax.device_get(w)
            del w
        self.net = Network()
        self.parent = NodeRuntime("parent", self.net, pool_frames=self.npages)
        with sp.span("setup.seed_parent"):
            inst = ModelInstance.create(self.parent, self.cfg.name, host)
            del host
            self.handle = self.parent.prepare_fork(inst)
        with sp.span("setup.child_node"):
            self.child_node = NodeRuntime("child", self.net,
                                          pool_frames=self.npages,
                                          device_pool=True)

    def fork(self):
        """resume_on (lazy) and materialize: (child instance, params)."""
        with self.sp.span("resume"):
            child = self.handle.resume_on(self.child_node,
                                          ForkPolicy(lazy=True))
        with self.sp.span("materialize"):
            params = jax.block_until_ready(child.materialize_pytree())
        return child, params

    def engine(self, params, kv_frames: int = 0,
               page_tokens: int = 16) -> ServingEngine:
        """A serving engine over ``params``; ``kv_frames`` > 0 gives its KV
        pool that many frames up front (a server's fixed KV capacity)."""
        eng = ServingEngine(self.cfg, params, page_tokens=page_tokens,
                            backend="auto")
        if kv_frames:
            dm = self.dm
            eng.kv = PagedKV(dm.layers, dm.kv_heads, dm.head_dim,
                             page_tokens=page_tokens,
                             dtype=jnp.dtype(self.cfg.compute_dtype),
                             pool=PagePool(page_tokens * dm.kv_heads
                                           * dm.head_dim,
                                           initial_frames=kv_frames))
        return eng

    def release(self) -> None:
        for name in ("handle", "parent", "child_node", "net"):
            self.__dict__.pop(name, None)


def step_and_stamp(eng, records: dict, t0: float) -> None:
    """One engine step; each token it made gets the host time it was on the
    host, in seconds after ``t0``.  ``records`` maps request id -> record."""
    before = {rid: len(eng.requests[rid].out_tokens) for rid in records}
    eng.step()
    now = time.perf_counter() - t0
    for rid, rec in records.items():
        made = len(eng.requests[rid].out_tokens) - before[rid]
        rec["times"].extend([now] * made)


@jax.jit
def leaf_sums(tree):
    """Per leaf, two wrapping uint32 sums of its bits: plain and weighted
    by position, so a changed, moved or missing page shows."""
    out = []
    for x in jax.tree.leaves(tree):
        bits = jax.lax.bitcast_convert_type(
            x, {2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize])
        b = bits.astype(jnp.uint32).ravel()
        i = jnp.arange(b.size, dtype=jnp.uint32)
        out.append(jnp.stack([jnp.sum(b), jnp.sum(b * (2 * i + 1))]))
    return jnp.stack(out)
