"""A configuration file made into the program's model, and its weights.

The model is the repository's registry architecture (``run.arch``) cut to
the file's depth; every width is checked against the file before a run.
The weights are the benchmark's own: one jitted call makes every leaf on
the device from ``--seed``, in the program's parameter layout and its master
dtype.  The reference makes them again the same way after the window, so it
takes nothing the program has made.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import GroupSpec, get_arch
from repro.memory.pool import PAGE_ELEMS
from repro.models import lm




@dataclasses.dataclass(frozen=True)
class Dims:
    """The numbers the harness, work counts and reference need."""
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    gated: bool
    tied: bool
    norm_eps: float
    rope_theta: float


def dims(conf: dict) -> Dims:
    run = conf["run"]
    w = run["widths"]
    return Dims(layers=int(conf[run["depth_key"]]), d=int(conf[w["d_model"]]),
                heads=int(conf[w["num_heads"]]),
                kv_heads=int(conf[w["num_kv_heads"]]) if "num_kv_heads" in w
                else int(run["num_kv_heads"]),
                head_dim=int(run["head_dim"]), d_ff=int(conf[w["d_ff"]]),
                vocab=int(conf[w["vocab_size"]]),
                gated=run["mlp"] == "gated_silu",
                tied=bool(run["tied_embeddings"]),
                norm_eps=float(run["norm_eps"]),
                rope_theta=float(run["rope_theta"]))


def arch(conf: dict):
    """The registry architecture at the file's depth, its widths checked."""
    run, dm = conf["run"], dims(conf)
    base = get_arch(run["arch"])
    if len(base.groups) != 1 or len(base.groups[0].unit) != 1:
        raise ValueError(f"{run['arch']}: expected one repeated block")
    cfg = dataclasses.replace(
        base, name=conf["name"],
        groups=(GroupSpec(unit=base.groups[0].unit, repeat=dm.layers),),
        param_dtype=run["param_dtype"], compute_dtype=run["compute_dtype"])
    want = {"d_model": dm.d, "num_heads": dm.heads, "num_kv_heads": dm.kv_heads,
            "head_dim": dm.head_dim, "d_ff": dm.d_ff, "vocab_size": dm.vocab,
            "mlp_gated": dm.gated, "tie_embeddings": dm.tied,
            "norm_eps": dm.norm_eps, "rope_theta": dm.rope_theta}
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        raise ValueError(f"{conf['name']}: registry {got} != file {want}")
    return cfg


def seed_key(seed: int):
    """A PRNG key that every bit of a 64-bit seed changes."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def _std(path: str, dm: Dims) -> float:
    """Init scale of one leaf, by its name in the program's layout."""
    leaf = path.rsplit("/", 1)[-1]
    table = {"wq": dm.d, "wk": dm.d, "wv": dm.d, "wo": dm.heads * dm.head_dim,
             "wi": dm.d, "wg": dm.d, "wd": dm.d_ff, "out": dm.d}
    if leaf == "tok":
        return 0.02
    if leaf == "scale":
        return 0.1
    if leaf not in table:
        raise KeyError(f"no init scale for leaf {path}")
    return 1.0 / math.sqrt(table[leaf])


def leaf_paths(tree) -> list:
    return ["/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in p)
            for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def make_weights_fn(cfg, dm: Dims):
    """jit(seed key) -> the full parameter pytree, on the device."""
    shapes = jax.eval_shape(lambda k: lm.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    treedef = jax.tree.structure(shapes)
    specs = jax.tree.leaves(shapes)
    stds = [_std(p, dm) for p in leaf_paths(shapes)]

    @jax.jit
    def make(key):
        leaves = [std * jax.random.normal(jax.random.fold_in(key, i), s.shape,
                                          s.dtype)
                  for i, (s, std) in enumerate(zip(specs, stds))]
        return jax.tree.unflatten(treedef, leaves)

    return make


def param_pages(cfg) -> int:
    """Pool pages the model's parameters take, leaf by leaf."""
    shapes = jax.eval_shape(lambda k: lm.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    return sum(max(1, -(-x.size // PAGE_ELEMS)) for x in jax.tree.leaves(shapes))


def page_bytes(cfg) -> int:
    return PAGE_ELEMS * np.dtype(cfg.param_dtype).itemsize
