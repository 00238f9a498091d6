"""A configuration's block comes from the module its ``run.reference``
names: the dense module reproduces the parent commit's weights and
reference bit for bit, and a module that exists only here runs a whole
cell with no edit to the harness."""
import copy
import dataclasses
import hashlib
import itertools
import sys
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check, model, spec
from chipbench.references import dense
from chipbench.tests import tiny
from chipbench.tests.cells import run_tiny
from repro.configs.base import GroupSpec, get_arch

# Digests of what the parent commit's chipbench/model.py and reference.py
# gave at tiny widths on the CPU, before the block moved into
# references/dense.py: the leaves' bytes in order, the reference's float32
# and fp8 logits at positions 500..599 of a 600-token prompt (two query
# blocks), and served_gaps of four tokens after a 37-token prompt.
PARENT = {
    "granite-34b": {"weights": "b05143d2c9a80929", "logits": "5feea2a3170beed7",
                    "logits_fp8": "ccb56e1ef7d6ff02", "gaps": "86741eb74c3518f2"},
    "stablelm-3b": {"weights": "ed42cb31ca22660c", "logits": "ace9e99135412668",
                    "logits_fp8": "e677e0d46cb764ec", "gaps": "84f5c3a9b3083d93"},
}
# granite's layout (one kv head, GELU MLP, tied head) and stablelm's
# (grouped kv heads, SwiGLU, untied head), each at tiny widths
TINY = {"granite-34b": dict(kv_heads=1, gated=False, tied=True),
        "stablelm-3b": dict(kv_heads=4, gated=True, tied=False)}


def _digest(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(np.asarray(a)).tobytes())
    return h.hexdigest()[:16]


def _tiny_model(arch: str) -> model.Model:
    t = TINY[arch]
    base = get_arch(arch)
    cfg = dataclasses.replace(
        base, name=arch + "-tiny", d_model=64, num_heads=4,
        num_kv_heads=t["kv_heads"], head_dim=16, d_ff=128, vocab_size=256,
        groups=(GroupSpec(unit=base.groups[0].unit, repeat=2),),
        param_dtype="float32", compute_dtype="bfloat16")
    dm = dense.Dims(layers=2, d=64, heads=4, kv_heads=t["kv_heads"],
                    head_dim=16, d_ff=128, vocab=256, gated=t["gated"],
                    tied=t["tied"], norm_eps=1e-5, rope_theta=1e4)
    return model.Model(dense, dm, cfg)


@pytest.mark.parametrize("arch", sorted(PARENT))
def test_dense_reproduces_the_parents_weights_and_reference(arch):
    m = _tiny_model(arch)
    w = model.make_weights_fn(m)(model.seed_key(2**31 + 12345))
    toks = jnp.asarray(np.random.default_rng(7).integers(0, 256, 600),
                       jnp.int32)
    got = {"weights": _digest(*jax.tree.leaves(w)),
           "logits": _digest(dense.logits_at(w, toks, dm=m.dims, first=500)),
           "logits_fp8": _digest(dense.logits_at(w, toks, dm=m.dims,
                                                 first=500, fp8=True)),
           "gaps": _digest(check.served_gaps(m, w, list(range(3, 40)),
                                             [5, 9, 200, 17]))}
    assert got == PARENT[arch]


def _block_module(name: str, monkeypatch, perturb: bool = False):
    """A copy of the dense module, importable only inside this test as
    ``chipbench.references.<name>``.  ``perturb`` zeroes the second
    layer's attention output in its forward pass."""
    mod = types.ModuleType(f"chipbench.references.{name}")
    monkeypatch.setitem(sys.modules, mod.__name__, mod)
    src = Path(dense.__file__).read_text()
    exec(compile(src, mod.__name__, "exec"), mod.__dict__)
    if perturb:
        attention, calls = mod._attention, itertools.count()

        def one_layer_off(q, k, v, fp8):
            out = attention(q, k, v, fp8)
            return out * 0.0 if next(calls) % 2 == 1 else out

        mod._attention = one_layer_off
    conf = copy.deepcopy(tiny.TINY_CONF)
    conf["run"]["reference"] = name
    return conf


def test_a_block_module_found_by_name_alone(monkeypatch):
    conf = _block_module("test_only_block", monkeypatch)
    assert spec.reference_module(conf) is sys.modules[
        "chipbench.references.test_only_block"]
    r = run_tiny("serve", conf=conf)
    assert r["correct"] and r["failed"] == 0


def test_a_module_that_perturbs_one_layer_is_not_correct(monkeypatch):
    conf = _block_module("test_only_perturbed", monkeypatch, perturb=True)
    r = run_tiny("serve", conf=conf)
    assert not r["correct"]
    c = r["checks"]["served_logit_gap"]
    assert c["value"] > c["limit"]
