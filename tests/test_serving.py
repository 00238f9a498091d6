"""Serving engine + paged KV: decode parity, COW fork, refcounts."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_arch
from repro.models import lm
from repro.serving.engine import ServingEngine
from repro.serving.kv_cache import PagedKV


@pytest.fixture(scope="module")
def setup():
    cfg = dataclasses.replace(get_arch("micro-hello"), compute_dtype="float32")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _reference_greedy(cfg, params, prompt, n):
    toks = jnp.asarray(prompt, jnp.int32)[None]
    logits, caches = lm.prefill(params, cfg, toks, cache_len=64)
    out = [int(jnp.argmax(logits[0]))]
    for t in range(n - 1):
        pos = jnp.asarray([len(prompt) + t], jnp.int32)
        logits, caches = lm.decode_step(
            params, cfg, caches, jnp.asarray([out[-1]], jnp.int32), pos)
        out.append(int(jnp.argmax(logits[0])))
    return out


@pytest.mark.parametrize("backend", ["ref", "kernel"])
def test_engine_matches_model(setup, backend):
    cfg, params = setup
    prompt = [5, 9, 2, 77, 31]
    ref = _reference_greedy(cfg, params, prompt, 6)
    eng = ServingEngine(cfg, params, page_tokens=4, backend=backend)
    rid = eng.submit(prompt, max_tokens=6)
    assert eng.run_to_completion()[rid] == ref


def test_engine_continuous_batching(setup):
    cfg, params = setup
    eng = ServingEngine(cfg, params, page_tokens=4, backend="ref")
    prompts = [[1, 2, 3], [9, 8, 7, 6], [42]]
    refs = [_reference_greedy(cfg, params, p, 4) for p in prompts]
    rids = [eng.submit(p, max_tokens=4) for p in prompts]
    res = eng.run_to_completion()
    for rid, ref in zip(rids, refs):
        assert res[rid] == ref


def test_fork_request_zero_copy_and_divergence(setup):
    cfg, params = setup
    prompt = [3, 1, 4, 1, 5]
    # reference: parent alone
    eng0 = ServingEngine(cfg, params, page_tokens=4, backend="ref")
    r_ref = eng0.submit(prompt, max_tokens=8)
    ref = eng0.run_to_completion()[r_ref]

    eng = ServingEngine(cfg, params, page_tokens=4, backend="ref")
    r0 = eng.submit(prompt, max_tokens=8)
    eng.step()
    eng.step()
    b0 = eng.kv.bytes_in_use()
    k1 = eng.fork_request(r0, max_tokens=6)
    assert eng.kv.bytes_in_use() == b0          # COW: no page copied at fork
    # diverge the child: force a different continuation token
    eng.requests[k1].prompt[-1] = 123
    res = eng.run_to_completion()
    # a divergent child must never corrupt the parent (COW isolation)
    assert res[r0] == ref
    assert res[k1] != ref[3:3 + 6]


def test_paged_kv_refcount_free(setup):
    cfg, params = setup
    kv = PagedKV(2, 2, 16, page_tokens=4, dtype=jnp.float32)
    s0 = kv.new_seq()
    k = jnp.ones((2, 6, 2, 16))
    kv.write_prefill(s0, k, k)
    used0 = kv.pool.num_allocated(jnp.float32)
    s1 = kv.fork_sequence(s0)
    kv.free_seq(s0)
    assert kv.pool.num_allocated(jnp.float32) == used0  # child holds pages
    kv.free_seq(s1)
    assert kv.pool.num_allocated(jnp.float32) == 0


def test_cow_write_after_fork_isolates(setup):
    kv = PagedKV(1, 1, 8, page_tokens=4, dtype=jnp.float32)
    s0 = kv.new_seq()
    # 3 tokens: the first page column is only partially filled
    kv.write_prefill(s0, jnp.ones((1, 3, 1, 8)), jnp.ones((1, 3, 1, 8)))
    s1 = kv.fork_sequence(s0)
    # child appends into the shared partial column -> COW
    kv.append_token(s1, jnp.full((1, 1, 8), 9.0), jnp.full((1, 1, 8), 9.0))
    f = kv.frames_view()
    parent_page = kv.seqs[s0].k_pages[0, 0]
    child_page = kv.seqs[s1].k_pages[0, 0]
    assert parent_page != child_page
    np.testing.assert_array_equal(np.asarray(f[parent_page, :, :3]),
                                  np.ones((1, 3, 8), np.float32))
    np.testing.assert_array_equal(np.asarray(f[child_page, :, 3]),
                                  np.full((1, 8), 9.0, np.float32))


def test_windowed_arch_decode_in_engine():
    cfg = dataclasses.replace(get_arch("micro-hello"), compute_dtype="float32")
    # add a windowed layer variant
    from repro.configs.base import ArchConfig, AttnSpec, GroupSpec
    import dataclasses as dc
    cfg = dc.replace(cfg, groups=(GroupSpec(unit=(AttnSpec(window=8),), repeat=2),),
                     name="micro-win")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    prompt = [1, 2, 3, 4, 5, 6]
    eng = ServingEngine(cfg, params, page_tokens=4, backend="ref")
    rid = eng.submit(prompt, max_tokens=4)
    out = eng.run_to_completion()[rid]
    assert len(out) == 4
