"""The fork-per-invocation cell end to end on the CPU, sound and with the
timed path broken underneath: ``correct`` must follow."""
import jax
import jax.numpy as jnp

from chipbench.tests.cells import run_tiny
from repro.core.instance import ModelInstance
from repro.serving import engine


def test_sound_run_is_correct():
    r = run_tiny("fork")
    assert r["correct"] and r["failed"] == 0 and r["attempted"] == 4
    assert r["checks"]["child_params_differ"]["value"] == 0
    assert set(r["metrics"]) == {"invoke_ms_p95", "hbm_peak_gib", "setup_s"}
    assert list(r)[-1] == "checks"


def test_altered_child_page_is_caught(monkeypatch):
    real = ModelInstance.materialize_pytree

    def corrupt(self):
        tree = real(self)
        leaves, treedef = jax.tree.flatten(tree)
        leaves[-1] = leaves[-1].at[0].add(1.0)
        return jax.tree.unflatten(treedef, leaves)

    monkeypatch.setattr(ModelInstance, "materialize_pytree", corrupt)
    r = run_tiny("fork")
    assert not r["correct"]
    assert r["checks"]["child_params_differ"]["value"] > 0


def test_altered_token_is_caught(monkeypatch):
    monkeypatch.setattr(engine, "sample", lambda logits, key: (
        jnp.argmax(logits, -1) + 1).astype(jnp.int32) % logits.shape[-1])
    r = run_tiny("fork")
    assert not r["correct"]
    c = r["checks"]["served_logit_gap"]
    assert c["value"] > c["limit"]
