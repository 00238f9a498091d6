#!/usr/bin/env python3
"""Chip smoke test: the fork-and-serve path, once, on a TPU at full width.

  python chip_smoke.py               # one chip: gemma3-1b, then micro-large
  python chip_smoke.py --four-chips  # four chips: elastic dp=2 -> dp=4

One chip, per model (``fork_phase`` then ``serve_phase``):

* fork — seed the model from ``lm.init_params`` on a parent node (host page
  pool; the device copy of the parameters is dropped), fork it lazily to a
  child node whose page pool lives on the device, materialize the child and
  require every leaf to equal the parent's bit for bit;
* serve — greedy requests and one ``fork_request`` through a
  ``ServingEngine`` with ``backend="auto"`` (the paged_attention kernel),
  then the same through ``backend="ref"`` on the same child parameters.
  Every request's first token must agree, and at every decode step the
  kernel's attention over the engine's live KV pages must agree with the
  reference within ``ATTN_TOL``.

Afterwards page_gather, cow_scatter and paged_attention must have resolved
to compiled Pallas and nothing else on the kernel engines' path; the ref
engine's meters are kept apart.

Four chips (``elastic_phase``): the data-parallel elastic resize of
``examples/train_elastic.py`` at train-100m width — a few steps at dp=2,
two workers remote-fork the state and join, the same number of steps at
dp=4 — against the same batches on one chip.

Without a TPU the script exits non-zero and prints no result.  The last
line of a passing run is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import itertools
import json
import os
import sys
import time
from collections import Counter

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec  # noqa: E402

from repro.configs.base import get_arch  # noqa: E402
from repro.core.descriptor import flatten_with_names  # noqa: E402
from repro.core.instance import ModelInstance  # noqa: E402
from repro.distributed import ctx  # noqa: E402
from repro.distributed.sharding import (make_axis_env,  # noqa: E402
                                        params_shardings, token_sharding)
from repro.fork import ForkPolicy  # noqa: E402
from repro.kernels import dispatch  # noqa: E402
from repro.kernels.paged_attention.ops import paged_attention  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_dp_mesh  # noqa: E402
from repro.memory.paging import num_pages  # noqa: E402
from repro.memory.pool import PAGE_ELEMS  # noqa: E402
from repro.models import lm  # noqa: E402
from repro.models.flops import param_counts  # noqa: E402
from repro.net import Network  # noqa: E402
from repro.platform.node import NodeRuntime  # noqa: E402
from repro.serving.engine import ServingEngine  # noqa: E402
from repro.training.data import TokenStream  # noqa: E402
from repro.training.optimizer import init_opt_state  # noqa: E402
from repro.training.train_step import (TrainConfig,  # noqa: E402
                                       make_train_step)

SEED = 0            # random weights, prompts and batches are made from it
KERNELS = ("page_gather", "cow_scatter", "paged_attention")
IMPLS = (dispatch.IMPL_KERNEL, dispatch.IMPL_INTERPRET, dispatch.IMPL_JNP,
         dispatch.IMPL_REF)
# Kernel vs reference attention output, as max |kernel - ref| over
# max(1, max |ref|).  Both accumulate in f32 and round the output once to
# the compute dtype; bf16 keeps 8 significant bits (a step of 2^-8 = 0.4%
# relative), so 2e-2 is a few rounding steps and far below a wrong page,
# mask or head (which moves outputs by O(1)).
ATTN_TOL = 2e-2
# Per-step training loss against the one-chip run on the same batches:
# data parallelism only reorders the gradient reduction, which in bf16
# compute moves the loss (about 10.4 at init) in its third digit at most.
LOSS_TOL = 5e-2


# one compiled program instead of an eager op per initializer
_init_params = jax.jit(lm.init_params, static_argnums=1)


class SmokeFailure(Exception):
    """A phase produced a wrong result."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _timed(log, label: str, fn):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    log(f"[smoke]   {label}: {time.perf_counter() - t0:.3f} s")
    return out


def _same_bits(a, b) -> bool:
    a, b = np.ascontiguousarray(a), np.ascontiguousarray(np.asarray(b))
    return (a.shape == b.shape and a.dtype == b.dtype
            and np.array_equal(a.view(np.uint8), b.view(np.uint8)))


def _check_same_tree(want, got, what: str) -> int:
    names, _, want_leaves = flatten_with_names(want)
    got_names, _, got_leaves = flatten_with_names(got)
    check(names == got_names, f"{what}: leaf names differ")
    bad = [n for n, a, b in zip(names, want_leaves, got_leaves)
           if not _same_bits(a, b)]
    check(not bad, f"{what}: {len(bad)} leaves differ, first {bad[:3]}")
    return len(names)


# ---------------------------------------------------------------------------
# one chip: fork, then serve
# ---------------------------------------------------------------------------


def fork_phase(cfg, *, seed: int, backend: str, log=print):
    """Seed ``cfg`` on a parent node, fork it to a device-pool child and
    materialize it.  Returns (child params, the network meter)."""
    shapes = jax.eval_shape(_init_params, jax.random.PRNGKey(seed), cfg)
    leaves = jax.tree.leaves(shapes)
    npages = sum(num_pages(x.size, PAGE_ELEMS) for x in leaves)
    log(f"[smoke] {cfg.name}: {sum(x.size for x in leaves) / 1e9:.3f}B "
        f"params, {npages} pages of {PAGE_ELEMS} elements")
    net = Network()
    parent = NodeRuntime("parent", net, pool_frames=npages)
    host = _timed(log, "init params (device copy dropped)",
                  lambda: jax.device_get(
                      _init_params(jax.random.PRNGKey(seed), cfg)))
    seed_inst = _timed(log, "seed on parent",
                       lambda: ModelInstance.create(parent, cfg.name, host))
    handle = _timed(log, "prepare_fork",
                    lambda: parent.prepare_fork(seed_inst))
    # the child's pool holds the whole model from the start, so it never
    # grows by concatenation on the device
    child_node = NodeRuntime("child", net, pool_frames=npages,
                             device_pool=True, kernel_backend=backend)
    child = _timed(log, "resume_on (lazy)",
                   lambda: handle.resume_on(child_node, ForkPolicy(lazy=True)))
    params = _timed(log, "materialize", child.materialize_pytree)
    blob = len(parent.seeds[handle.handler_id].blob)
    log(f"[smoke]   descriptor {blob} B, {child.stats['pages_rdma']} pages "
        f"moved, {net.meter['page_pages_moved']} on the wire, child pool "
        f"{child_node.pool.bytes_reserved() / 2**30:.3f} GiB on "
        f"{jax.devices()[0].platform}")
    reserved = sum(npages * PAGE_ELEMS * np.dtype(d).itemsize
                   for d in {x.dtype for x in leaves})
    check(child_node.pool.bytes_reserved() == reserved,
          f"device pool grew: {child_node.pool.bytes_reserved()} B reserved, "
          f"{reserved} B expected")
    n = _check_same_tree(host, params, f"{cfg.name} child vs parent")
    log(f"[smoke]   child == parent bit for bit over {n} leaves")
    return params, net.meter


def _probe_layers(specs):
    """The first windowed and the first global attention layer."""
    found = {}
    for i, s in enumerate(specs):
        found.setdefault(s.window is None, i)
    return sorted(found.values())


def attention_probe(eng, *, key, backend: str, kernel_meter, ref_meter):
    """Run ``backend`` and the reference over the engine's live KV pages,
    tables, lengths and windows for a seeded query; returns the largest
    relative difference over the probed layers (None with nothing active)."""
    sids = [eng.requests[r].seq_id for r in eng.active]
    if not sids:
        return None
    cfg = eng.cfg
    k_pt, v_pt, lens = eng.kv.batch_tables(sids)
    frames = eng.kv.frames_view()
    q = jax.random.normal(key, (len(sids), cfg.num_kv_heads,
                                cfg.num_heads // cfg.num_kv_heads,
                                cfg.head_dim), eng.kv.dtype)
    worst = 0.0
    for li in _probe_layers(eng.specs):
        w = eng.specs[li].window
        starts = jnp.maximum(lens - w, 0) if w is not None else None
        args = (q, frames, frames, k_pt[:, li], lens)
        kw = dict(v_page_table=v_pt[:, li], starts=starts)
        got = paged_attention(*args, **kw, backend=backend)
        dispatch.drain_meters_into(kernel_meter)
        want = paged_attention(*args, **kw, backend="ref")
        dispatch.drain_meters_into(ref_meter)
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        check(np.isfinite(got).all(), f"layer {li}: non-finite attention")
        worst = max(worst, float(np.abs(got - want).max()
                                 / max(1.0, float(np.abs(want).max()))))
    return worst


def _drive(cfg, params, prompts, new_tokens: int, backend: str, meter,
           probe=None):
    """Serve ``prompts`` plus one fork_request of the first; returns
    (tokens per request, step at which each token was made, probe per
    step)."""
    eng = ServingEngine(cfg, params, backend=backend)
    rids = [eng.submit(p, max_tokens=new_tokens) for p in prompts]
    made, probes = {}, []

    def step():
        before = {r: len(q.out_tokens) for r, q in eng.requests.items()}
        eng.step()
        dispatch.drain_meters_into(meter)
        for r, q in eng.requests.items():
            for i in range(before.get(r, 0), len(q.out_tokens)):
                made[(r, i)] = len(probes)
        probes.append(probe(eng) if probe else None)

    step()
    step()                      # the first request is live: fork it
    rids.append(eng.fork_request(rids[0], max_tokens=new_tokens))
    limit = 4 * (len(prompts) + new_tokens)
    while (eng.waiting or eng.active) and len(probes) < limit:
        step()
    check(not (eng.waiting or eng.active),
          f"{backend} engine did not finish in {limit} steps")
    return {r: list(eng.requests[r].out_tokens) for r in rids}, made, probes


def serve_phase(cfg, params, *, backend: str, seed: int,
                prompt_lens=(16, 32, 48, 64), new_tokens: int = 16,
                log=print):
    """Serve from ``params`` through ``backend`` and through the reference;
    returns (kernel-path meter, ref-path meter)."""
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in prompt_lens]
    kernel_meter, ref_meter = Counter(), Counter()
    key, steps = jax.random.PRNGKey(seed), itertools.count()

    def probe(eng):
        return attention_probe(eng, key=jax.random.fold_in(key, next(steps)),
                               backend=backend,
                               kernel_meter=kernel_meter, ref_meter=ref_meter)

    t0 = time.perf_counter()
    got, made, probes = _drive(cfg, params, prompts, new_tokens, backend,
                               kernel_meter, probe)
    t1 = time.perf_counter()
    want, _, _ = _drive(cfg, params, prompts, new_tokens, "ref", ref_meter)
    t2 = time.perf_counter()
    n_tok = sum(len(t) for t in got.values())
    log(f"[smoke]   serve {len(got)} requests (prompts {list(prompt_lens)} "
        f"+ 1 fork_request, {new_tokens} new tokens): {backend} engine "
        f"{t1 - t0:.3f} s, ref engine {t2 - t1:.3f} s, {n_tok} tokens, "
        f"{len(probes)} steps (compilation included)")
    seen = [p for p in probes if p is not None]
    check(seen, "no decode step was probed")
    worst = max(seen)
    log(f"[smoke]   attention {backend} vs ref: max relative diff "
        f"{worst:.3e} over {len(seen)} steps (tolerance {ATTN_TOL:g})")
    agree = sum(a == b for r in got for a, b in zip(got[r], want[r]))
    log(f"[smoke]   tokens agreeing with ref: {agree}/{n_tok}")
    for r in got:
        diff = [i for i, (a, b) in enumerate(zip(got[r], want[r])) if a != b]
        if diff:
            i = diff[0]
            log(f"[smoke]   request {r}: token {i} differs ({backend} "
                f"{got[r][i]}, ref {want[r][i]}); attention diff at that "
                f"step {probes[made[(r, i)]]}")
    check(all(got[r][:1] == want[r][:1] for r in got),
          "a request's first token differs from the ref engine")
    check(worst <= ATTN_TOL,
          f"attention differs from ref by {worst:.3e} > {ATTN_TOL:g}")
    return kernel_meter, ref_meter


def check_kernel_meters(meters, impl: str) -> dict:
    """Every kernel ran, and only as ``impl``.  Returns the per-kernel
    counts."""
    out = {}
    for name in KERNELS:
        counts = {i: meters.get(f"kernel.{name}.{i}", 0) for i in IMPLS}
        check(counts[impl] > 0, f"{name} never ran as {impl}: {counts}")
        other = {i: n for i, n in counts.items() if i != impl and n}
        check(not other, f"{name} also resolved to {other}")
        out[name] = counts[impl]
    return out


def fork_serve(cfg, *, backend: str, impl: str, seed: int, log=print,
               **serve_kw):
    """Both one-chip phases for ``cfg``; returns the kernel-path meter."""
    dispatch.reset_meters()     # count only what this smoke resolves
    params, net_meter = fork_phase(cfg, seed=seed, backend=backend, log=log)
    kernel_meter, ref_meter = serve_phase(cfg, params, backend=backend,
                                          seed=seed, log=log, **serve_kw)
    kernel_meter.update({k: v for k, v in net_meter.items()
                         if k.startswith("kernel.")})
    counts = check_kernel_meters(kernel_meter, impl)
    refs = {k: v for k, v in ref_meter.items() if v}
    check(set(refs) == {"kernel.paged_attention.ref"},
          f"ref engine meters: {refs}")
    log(f"[smoke]   kernel meters: {counts} all {impl}; ref engine: {refs}")
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    log(f"[smoke]   device memory peak so far: "
        f"{'not reported' if peak is None else f'{peak / 2**30:.3f} GiB'}")
    return kernel_meter


# ---------------------------------------------------------------------------
# four chips: elastic data-parallel resize
# ---------------------------------------------------------------------------


def _train(cfg, tcfg, stream, mesh, state, steps):
    """Run ``steps`` of the train step on ``mesh`` from the host ``state``;
    returns (params, opt state, losses)."""
    env = make_axis_env(mesh)
    with ctx.use_env(env):
        step_fn = jax.jit(make_train_step(cfg, tcfg), donate_argnums=(0, 1))
        sh = params_shardings(cfg, jax.eval_shape(lambda: state["params"]),
                              env)
        params = jax.device_put(state["params"], sh)
        opt = {"m": jax.device_put(state["opt"]["m"], sh),
               "v": jax.device_put(state["opt"]["v"], sh),
               "count": jax.device_put(state["opt"]["count"],
                                       NamedSharding(mesh, PartitionSpec()))}
        tok_sh = token_sharding(cfg, stream.batch, env)
        losses = []
        for s in steps:
            tok, lab = stream.batch_at(s)
            params, opt, m = step_fn(params, opt, jax.device_put(tok, tok_sh),
                                     jax.device_put(lab, tok_sh))
            losses.append(float(m["loss"]))
    return params, opt, losses


def elastic_phase(cfg, *, seed: int, steps: int = 3, batch: int = 8,
                  seq: int = 256, log=print):
    """dp=2 for ``steps`` steps, two workers join by remote fork, dp=4 for
    ``steps`` more; compared with one chip on the same batches."""
    check(len(jax.devices()) >= 4,
          f"needs 4 devices, JAX has {len(jax.devices())}")
    n = 2 * steps
    tcfg = TrainConfig(peak_lr=1e-3, warmup=2, total_steps=n, q_chunk=seq,
                       xent_chunk=seq)
    stream = TokenStream(cfg.vocab_size, batch, seq, seed=seed)
    log(f"[smoke] {cfg.name}: {param_counts(cfg)[0] / 1e6:.1f}M params, "
        f"batch {batch} x {seq} tokens, {steps} steps at dp=2 then "
        f"{steps} at dp=4")
    params = _init_params(jax.random.PRNGKey(seed), cfg)
    state0 = jax.device_get({"params": params, "opt": init_opt_state(params)})
    del params

    t0 = time.perf_counter()
    _, _, ref = _train(cfg, tcfg, stream, make_dp_mesh(1), state0, range(n))
    log(f"[smoke]   one chip: losses {ref} "
        f"({time.perf_counter() - t0:.3f} s, compilation included)")

    t0 = time.perf_counter()
    p2, o2, losses = _train(cfg, tcfg, stream, make_dp_mesh(2), state0,
                            range(steps))
    state2 = jax.device_get({"params": p2, "opt": o2})
    del p2, o2
    log(f"[smoke]   dp=2: losses {losses} ({time.perf_counter() - t0:.3f} s)")

    net = Network()
    donor = NodeRuntime("donor", net)
    handle = donor.prepare_fork(ModelInstance.create(
        donor, cfg.name, state2, registers={"step": steps}))
    joined = None
    for i in range(2):
        t0 = time.perf_counter()
        child = handle.resume_on(NodeRuntime(f"worker{i}", net),
                                 ForkPolicy(lazy=True))
        joined = jax.device_get(child.materialize_pytree())
        check(child.registers["step"] == steps, "step register lost")
        _check_same_tree(state2, joined, f"worker{i} vs donor")
        log(f"[smoke]   worker{i} joined by remote fork: "
            f"{child.stats['pages_rdma']} pages, "
            f"{time.perf_counter() - t0:.3f} s, state equal bit for bit")

    mesh4 = make_dp_mesh(4)
    ids = sorted({d.id for d in mesh4.devices.flat})
    check(len(ids) == 4, f"dp=4 mesh spans devices {ids}")
    t0 = time.perf_counter()
    p4, _, more = _train(cfg, tcfg, stream, mesh4, joined, range(steps, n))
    losses += more
    log(f"[smoke]   dp=4: losses {more} ({time.perf_counter() - t0:.3f} s)")
    per_dev = Counter()
    split = 0
    for leaf in jax.tree.leaves(p4):
        split += any(s.data.shape != leaf.shape
                     for s in leaf.addressable_shards)
        for s in leaf.addressable_shards:
            per_dev[s.device.id] += s.data.nbytes
    log(f"[smoke]   dp=4 mesh devices {ids}; parameter bytes per device "
        f"{dict(sorted(per_dev.items()))}; {split} leaves split")
    check(sorted(per_dev) == ids and min(per_dev.values()) > 0,
          f"parameters not on every mesh device: {dict(per_dev)}")
    check(split > 0, "no parameter is sharded across the dp=4 mesh")
    check(all(np.isfinite(losses)), f"non-finite losses {losses}")
    worst = max(abs(a - b) for a, b in zip(losses, ref))
    log(f"[smoke]   elastic vs one chip: max |loss diff| {worst:.3e} "
        f"(tolerance {LOSS_TOL:g})")
    check(worst <= LOSS_TOL, f"losses {losses} vs one chip {ref}")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip elastic resize path")
    args = ap.parse_args(argv)
    enable_compile_cache()
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found {len(devs)} "
              f"{d0.platform} device(s) ({d0.device_kind})", file=sys.stderr)
        return 2
    print(f"[smoke] device: {d0.platform} {d0.device_kind} x{len(devs)}",
          flush=True)
    log = lambda msg: print(msg, flush=True)  # noqa: E731
    try:
        if args.four_chips:
            elastic_phase(get_arch("train-100m"), seed=SEED, log=log)
        else:
            dispatch.reset_meters()
            for arch in ("gemma3-1b", "micro-large"):
                fork_serve(get_arch(arch), backend="auto",
                           impl=dispatch.IMPL_KERNEL, seed=SEED, log=log)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {"platform": d0.platform,
                                             "kind": d0.device_kind,
                                             "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
