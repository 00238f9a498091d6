"""A configuration file made into the program's model, and its weights.

The model is the repository's registry architecture cut to the file's
depth by the file's reference module (``run.reference``, see
``references/``), which also checks every width against the file and gives
each leaf its init scale.  The weights are the benchmark's own: one jitted
call makes every leaf on the device from ``--seed``, in the program's
parameter layout and its master dtype.  The reference makes them again the
same way after the window, so it takes nothing the program has made.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np

from chipbench import spec
from repro.memory.pool import PAGE_ELEMS
from repro.models import lm


@dataclasses.dataclass(frozen=True)
class Model:
    """A configuration as the harness runs it: its reference module, the
    module's dims, and the program's architecture."""
    ref: object
    dims: object
    cfg: object


def load(conf: dict) -> Model:
    ref = spec.reference_module(conf)
    return Model(ref, ref.dims(conf), ref.arch(conf))


def seed_key(seed: int):
    """A PRNG key that every bit of a 64-bit seed changes."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def leaf_paths(tree) -> list:
    return ["/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in p)
            for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def _shapes(cfg):
    return jax.eval_shape(lambda k: lm.init_params(k, cfg),
                          jax.random.PRNGKey(0))


def make_weights_fn(m: Model):
    """jit(seed key) -> the full parameter pytree, on the device."""
    shapes = _shapes(m.cfg)
    treedef = jax.tree.structure(shapes)
    specs = jax.tree.leaves(shapes)
    stds = [m.ref.init_std(p, m.dims) for p in leaf_paths(shapes)]

    @jax.jit
    def make(key):
        leaves = [std * jax.random.normal(jax.random.fold_in(key, i), s.shape,
                                          s.dtype)
                  for i, (s, std) in enumerate(zip(specs, stds))]
        return jax.tree.unflatten(treedef, leaves)

    return make


def param_pages(cfg) -> int:
    """Pool pages the model's parameters take, leaf by leaf."""
    return sum(max(1, -(-x.size // PAGE_ELEMS))
               for x in jax.tree.leaves(_shapes(cfg)))


def page_bytes(cfg) -> int:
    return PAGE_ELEMS * np.dtype(cfg.param_dtype).itemsize
