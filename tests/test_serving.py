"""Serving engine + paged KV: decode parity, COW fork, refcounts."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_arch
from repro.memory.pool import PagePool
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


def _per_column_prefill(kv, sid, k, v):
    """The per-column write of a prefill: a page column of K and V at a
    time, each its own allocation and its own pool commit."""
    L, S = k.shape[:2]
    seq = kv.seqs[sid]
    ncols = -(-S // kv.Tp)
    for _ in range(ncols):
        kv._alloc_column(seq)
    padw = ((0, 0), (0, ncols * kv.Tp - S), (0, 0), (0, 0))
    shape = (L, ncols, kv.Tp, kv.K, kv.hd)
    k = jnp.pad(k, padw).reshape(shape).transpose(0, 1, 3, 2, 4)
    v = jnp.pad(v, padw).reshape(shape).transpose(0, 1, 3, 2, 4)
    for c in range(ncols):
        kv.pool.write_pages(kv.dtype, seq.k_pages[:, c], k[:, c].reshape(L, -1))
        kv.pool.write_pages(kv.dtype, seq.v_pages[:, c], v[:, c].reshape(L, -1))
    seq.length = S


def _kv(pool_kind, dtype):
    """2 layers, 2 kv heads of 16, 4-token pages: 128-element pages, which
    a device pool's tiles take whole."""
    pool = PagePool(128, device=pool_kind == "device",
                    kernel_backend="interpret")
    return PagedKV(2, 2, 16, page_tokens=4, dtype=dtype, pool=pool)


def _seq_pages(kv, sid):
    """A sequence's K then V pages as bytes, gathered through its tables."""
    seq = kv.seqs[sid]
    return np.concatenate([
        kv.pool.read_pages_host(kv.dtype, seq.k_pages.ravel()),
        kv.pool.read_pages_host(kv.dtype, seq.v_pages.ravel())]).view(np.uint8)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("S", [3, 8, 9])
@pytest.mark.parametrize("pool_kind", ["host", "device"])
def test_write_prefill_bulk_equals_per_column(pool_kind, S, dtype):
    L, K, hd, tp = 2, 2, 16, 4
    key_k, key_v = jax.random.split(jax.random.PRNGKey(S))
    k = jax.random.normal(key_k, (L, S, K, hd), jnp.float32)
    v = jax.random.normal(key_v, (L, S, K, hd), jnp.float32)
    ref = _kv(pool_kind, dtype)
    _per_column_prefill(ref, ref.new_seq(), k, v)
    kv = _kv(pool_kind, dtype)
    sid = kv.new_seq()
    kv.write_prefill(sid, k, v)
    ncols = -(-S // tp)
    seq = kv.seqs[sid]
    assert seq.length == S
    assert seq.k_pages.shape == seq.v_pages.shape == (L, ncols)
    assert not seq.shared_mask.any() and seq.shared_mask.shape == (ncols,)
    assert kv.pool.num_allocated(dtype) == 2 * L * ncols
    assert set(kv.refcount.values()) == {1}
    np.testing.assert_array_equal(_seq_pages(kv, sid), _seq_pages(ref, 0))
    kv.free_seq(sid)
    assert kv.pool.num_allocated(dtype) == 0 and not kv.refcount


@pytest.mark.parametrize("pool_kind", ["host", "device"])
def test_cow_append_after_bulk_prefill_and_fork(pool_kind):
    """9 tokens over 2 layers: two full page columns and a partial third.
    The child's append privatizes the partial column alone; the parent's
    pages stay as they were."""
    L, K, hd = 2, 2, 16
    kv = _kv(pool_kind, jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (L, 9, K, hd), jnp.float32)
    s0 = kv.new_seq()
    kv.write_prefill(s0, k, -k)
    before = _seq_pages(kv, s0)
    s1 = kv.fork_sequence(s0)
    row = jnp.full((L, K, hd), 7.0)
    kv.append_token(s1, row, -row)
    p, c = kv.seqs[s0], kv.seqs[s1]
    np.testing.assert_array_equal(c.k_pages[:, :2], p.k_pages[:, :2])
    np.testing.assert_array_equal(c.v_pages[:, :2], p.v_pages[:, :2])
    assert not np.isin(c.k_pages[:, 2], p.k_pages).any()
    assert not np.isin(c.v_pages[:, 2], p.v_pages).any()
    assert c.shared_mask.tolist() == [True, True, False]
    np.testing.assert_array_equal(_seq_pages(kv, s0), before)
    f = np.asarray(kv.frames_view())
    for layer in range(L):
        child, parent = f[c.k_pages[layer, 2]], f[p.k_pages[layer, 2]]
        np.testing.assert_array_equal(child[:, 0], parent[:, 0])
        np.testing.assert_array_equal(child[:, 1], np.full((K, hd), 7.0))
        np.testing.assert_array_equal(parent[:, 1], np.zeros((K, hd)))
        np.testing.assert_array_equal(f[c.v_pages[layer, 2]][:, 1],
                                      np.full((K, hd), -7.0))
    kv.free_seq(s0)
    assert kv.pool.num_allocated(jnp.float32) == 2 * L * 3
    kv.free_seq(s1)
    assert kv.pool.num_allocated(jnp.float32) == 0


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
