"""End-to-end training driver with fault tolerance and MITOSIS-style elastic
scale-up: train a ~100M-param LM, checkpoint/restart after a simulated crash,
then add a worker that joins by REMOTE-FORKING a healthy peer (descriptor +
on-demand page pull) instead of restoring from the checkpoint — the paper's
"no provisioned concurrency" applied to elastic training.

Runs on 8 forced host devices so the data-parallel resize 2 -> 4 is real.

  PYTHONPATH=src python examples/train_elastic.py [--steps 60] [--full-100m]
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_arch, reduce_for_smoke
from repro.core.instance import ModelInstance
from repro.net import Network
from repro.fork import ForkPolicy
from repro.distributed import ctx
from repro.distributed.sharding import make_axis_env, params_shardings
from repro.launch.mesh import make_dp_mesh
from repro.models import lm
from repro.models.flops import param_counts
from repro.platform.node import NodeRuntime
from repro.training import checkpoint as ckpt
from repro.training.data import TokenStream
from repro.training.optimizer import init_opt_state
from repro.training.train_step import TrainConfig, make_train_step


def shard_tree(tree, cfg, env):
    sh = params_shardings(cfg, jax.eval_shape(lambda: tree), env)
    return jax.tree.map(jax.device_put, tree, sh)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--full-100m", action="store_true",
                    help="use the full ~100M config (slow on CPU)")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    args = ap.parse_args()

    cfg = get_arch("train-100m")
    if not args.full_100m:
        cfg = dataclasses.replace(
            reduce_for_smoke(cfg), d_model=256, d_ff=1024, vocab_size=4096)
    cfg = dataclasses.replace(cfg, compute_dtype="float32")
    N, _, _ = param_counts(cfg)
    print(f"[elastic] {cfg.name}: {N/1e6:.1f}M params on "
          f"{len(jax.devices())} devices")

    tcfg = TrainConfig(peak_lr=1e-3, warmup=5, total_steps=args.steps,
                       q_chunk=args.seq, xent_chunk=args.seq)
    stream = TokenStream(cfg.vocab_size, args.batch, args.seq, seed=0)
    losses = []

    # ---- phase 1: dp=2, crash at 1/3 of the run, restart from checkpoint
    mesh2 = make_dp_mesh(2)
    env2 = make_axis_env(mesh2)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    opt = init_opt_state(params)
    with ctx.use_env(env2):
        step_fn = jax.jit(make_train_step(cfg, tcfg), donate_argnums=(0, 1))
        params = shard_tree(params, cfg, env2)
        opt["m"] = shard_tree(opt["m"], cfg, env2)
        opt["v"] = shard_tree(opt["v"], cfg, env2)
        crash_at = args.steps // 3
        for s in range(crash_at):
            tok, lab = stream.batch_at(s)
            params, opt, m = step_fn(params, opt, jnp.asarray(tok),
                                     jnp.asarray(lab))
            losses.append(float(m["loss"]))
        ckpt.save_checkpoint("/tmp/elastic_ckpt", crash_at, params, opt)
        print(f"[elastic] dp=2 trained to step {crash_at}, "
              f"loss {losses[0]:.4f} -> {losses[-1]:.4f}; CRASH (simulated)")

        # restart from checkpoint (classic fault tolerance)
        step0, params, opt, _ = ckpt.load_checkpoint("/tmp/elastic_ckpt")
        params = shard_tree(jax.tree.map(jnp.asarray, params), cfg, env2)
        opt = {"m": shard_tree(jax.tree.map(jnp.asarray, opt["m"]), cfg, env2),
               "v": shard_tree(jax.tree.map(jnp.asarray, opt["v"]), cfg, env2),
               "count": jnp.asarray(opt["count"])}
        for s in range(step0, 2 * args.steps // 3):
            tok, lab = stream.batch_at(s)
            params, opt, m = step_fn(params, opt, jnp.asarray(tok),
                                     jnp.asarray(lab))
            losses.append(float(m["loss"]))
        print(f"[elastic] restarted from step {step0}, continued to "
              f"{2*args.steps//3}, loss {losses[-1]:.4f}")

    # ---- phase 2: elastic scale-up 2 -> 4 via REMOTE FORK (no checkpoint IO)
    net = Network()
    donor = NodeRuntime("donor", net)
    joiner = NodeRuntime("joiner", net)
    state = {"params": jax.tree.map(np.asarray, params),
             "opt_m": jax.tree.map(np.asarray, opt["m"]),
             "opt_v": jax.tree.map(np.asarray, opt["v"])}
    inst = ModelInstance.create(donor, cfg.name, state,
                                registers={"step": 2 * args.steps // 3,
                                           "count": int(opt["count"])})
    handle = donor.prepare_fork(inst)
    t0 = time.perf_counter()
    child = handle.resume_on(joiner, ForkPolicy(lazy=True, prefetch=1))
    got = child.materialize_pytree()
    dt = time.perf_counter() - t0
    print(f"[elastic] worker joined via remote fork in {dt*1e3:.0f} ms "
          f"({child.stats['pages_rdma']} pages, descriptor "
          f"{len(donor.seeds[handle.handler_id].blob)} B — no checkpoint read)")

    mesh4 = make_dp_mesh(4)
    env4 = make_axis_env(mesh4)
    with ctx.use_env(env4):
        step_fn4 = jax.jit(make_train_step(cfg, tcfg), donate_argnums=(0, 1))
        params4 = shard_tree(jax.tree.map(jnp.asarray, got["params"]), cfg, env4)
        opt4 = {"m": shard_tree(jax.tree.map(jnp.asarray, got["opt_m"]), cfg, env4),
                "v": shard_tree(jax.tree.map(jnp.asarray, got["opt_v"]), cfg, env4),
                "count": jnp.asarray(child.registers["count"], jnp.int32)}
        start = child.registers["step"]
        for s in range(start, args.steps):
            tok, lab = stream.batch_at(s)
            params4, opt4, m = step_fn4(params4, opt4, jnp.asarray(tok),
                                        jnp.asarray(lab))
            losses.append(float(m["loss"]))
    print(f"[elastic] dp=4 continued to step {args.steps}, "
          f"final loss {losses[-1]:.4f}")
    assert losses[-1] < losses[0], "loss must decrease across crash + resize"
    print(f"[elastic] OK: {losses[0]:.4f} -> {losses[-1]:.4f} across "
          f"crash-restart and 2->4 elastic resize")


if __name__ == "__main__":
    main()
