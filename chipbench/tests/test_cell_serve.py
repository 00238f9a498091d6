"""The open-loop serving cell end to end on the CPU, sound and with the
timed path broken underneath: ``correct`` must follow.  The faults a served
cell can have are a token altered where it is produced and a step that
leaves its KV state unchanged; a decode that skips part of the batch only
delays those requests, which still finish with the right tokens."""
import jax.numpy as jnp

from chipbench.tests.cells import run_tiny
from repro.memory.pool import PagePool
from repro.serving import engine


def test_sound_run_is_correct():
    r = run_tiny("serve")
    assert r["correct"] and r["failed"] == 0 and r["attempted"] == 8
    assert set(r["metrics"]) == {"ttft_ms_p95", "itl_ms_p95", "hbm_peak_gib",
                                 "setup_s"}


def test_altered_token_is_caught(monkeypatch):
    monkeypatch.setattr(engine, "sample", lambda logits, key: (
        jnp.argmax(logits, -1) + 1).astype(jnp.int32) % logits.shape[-1])
    r = run_tiny("serve")
    assert not r["correct"]
    c = r["checks"]["served_logit_gap"]
    assert c["value"] > c["limit"]


def test_kv_state_left_unchanged_is_caught(monkeypatch):
    """Decode writes no new K/V: every later token attends stale pages."""
    monkeypatch.setattr(PagePool, "write_rows", lambda *a, **k: None)
    r = run_tiny("serve")
    assert not r["correct"]

