"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.cow_scatter.kernel import cow_scatter
from repro.kernels.cow_scatter.ref import cow_scatter_ref
from repro.kernels.page_gather.kernel import page_gather
from repro.kernels.page_gather.ops import page_gather as page_gather_op
from repro.kernels.page_gather.ref import page_gather_ref
from repro.kernels.paged_attention.kernel import paged_attention
from repro.kernels.paged_attention.ref import paged_attention_ref


@pytest.mark.parametrize("F,E,n", [(8, 128, 3), (32, 512, 32), (64, 1024, 1)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
def test_page_gather_sweep(F, E, n, dtype):
    key = jax.random.PRNGKey(F * E + n)
    if dtype == jnp.int32:
        frames = jax.random.randint(key, (F, E), 0, 1000)
    else:
        frames = jax.random.normal(key, (F, E), dtype)
    ids = jax.random.randint(key, (n,), 0, F)
    got = page_gather(frames, ids, interpret=True)
    want = page_gather_ref(frames, ids)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_page_gather_duplicate_ids():
    frames = jnp.arange(8 * 128, dtype=jnp.float32).reshape(8, 128)
    ids = jnp.array([5, 5, 5], jnp.int32)
    got = page_gather(frames, ids, interpret=True)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(jnp.stack([frames[5]] * 3)))


def test_page_gather_op_backend_switch():
    frames = jnp.arange(4 * 128, dtype=jnp.float32).reshape(4, 128)
    ids = jnp.array([2, 0], jnp.int32)
    for backend in ("auto", "kernel", "ref"):
        got = page_gather_op(frames, ids, backend=backend)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(frames[jnp.asarray(ids)]))


@pytest.mark.parametrize("F,E,n", [(8, 128, 3), (16, 256, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_cow_scatter_sweep(F, E, n, dtype):
    key = jax.random.PRNGKey(F + n)
    frames = jax.random.normal(key, (F, E), dtype)
    ids = np.random.default_rng(0).choice(F, size=n, replace=False).astype(np.int32)
    pages = jax.random.normal(jax.random.PRNGKey(1), (n, E), dtype)
    want = cow_scatter_ref(frames, jnp.asarray(ids), pages)
    # kernel donates `frames` (in-place COW commit) — call it last
    got = cow_scatter(frames, jnp.asarray(ids), pages, interpret=True)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def test_cow_scatter_leaves_other_frames():
    frames = jnp.ones((6, 128), jnp.float32)
    pages = jnp.zeros((1, 128), jnp.float32)
    got = cow_scatter(frames, jnp.array([3], jnp.int32), pages, interpret=True)
    assert float(got[3].sum()) == 0.0
    assert float(got[0].sum()) == 128.0


@pytest.mark.parametrize("B,K,G,hd,Tp,P,F", [
    (2, 2, 4, 128, 8, 4, 16),
    (1, 1, 8, 128, 16, 2, 8),       # MQA
    (3, 4, 1, 256, 8, 3, 24),       # MHA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_sweep(B, K, G, hd, Tp, P, F, dtype):
    keys = [jax.random.PRNGKey(i) for i in range(5)]
    q = jax.random.normal(keys[0], (B, K, G, hd), dtype)
    pk = jax.random.normal(keys[1], (F, K, Tp, hd), dtype)
    pv = jax.random.normal(keys[2], (F, K, Tp, hd), dtype)
    pt = jax.random.randint(keys[3], (B, P), 0, F)
    vt = jax.random.randint(keys[4], (B, P), 0, F)
    lengths = jax.random.randint(keys[4], (B,), 1, P * Tp + 1)
    got = paged_attention(q, pk, pv, pt, lengths, v_page_table=vt,
                          interpret=True)
    want = paged_attention_ref(q, pk, pv, pt, lengths, v_page_table=vt)
    tol = 3e-2 if dtype == jnp.bfloat16 else 3e-5
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                - want.astype(jnp.float32))))
    assert err < tol, err


def test_paged_attention_window_starts():
    B, K, G, hd, Tp, P, F = 2, 1, 2, 128, 8, 4, 12
    q = jax.random.normal(jax.random.PRNGKey(0), (B, K, G, hd))
    pk = jax.random.normal(jax.random.PRNGKey(1), (F, K, Tp, hd))
    pv = jax.random.normal(jax.random.PRNGKey(2), (F, K, Tp, hd))
    pt = jax.random.randint(jax.random.PRNGKey(3), (B, P), 0, F)
    lengths = jnp.array([30, 25], jnp.int32)
    starts = jnp.array([10, 0], jnp.int32)
    got = paged_attention(q, pk, pv, pt, lengths, starts=starts, interpret=True)
    want = paged_attention_ref(q, pk, pv, pt, lengths, starts=starts)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)
    # and starts matter
    want0 = paged_attention_ref(q, pk, pv, pt, lengths)
    assert float(jnp.abs(want - want0).max()) > 1e-4


# -- run-table (extent-run) variants, dispatch, fused assemble/patch ---------

from repro.kernels import dispatch
from repro.kernels.cow_scatter.ops import (cow_scatter as cow_scatter_op,
                                           cow_scatter_runs, scatter_patch)
from repro.kernels.page_gather.kernel import page_gather_runs as _pgr_kernel
from repro.kernels.page_gather.ops import (gather_assemble, page_gather_runs)
from repro.kernels.page_gather.ref import expand_runs

BACKENDS = ("auto", "kernel", "interpret", "jnp", "ref")


def test_expand_runs_matches_concat_of_aranges():
    rng = np.random.default_rng(7)
    for _ in range(20):
        k = int(rng.integers(1, 8))
        starts = rng.integers(0, 100, k)
        lens = rng.integers(0, 6, k)          # zero-length runs included
        want = np.concatenate(
            [np.arange(s, s + l) for s, l in zip(starts, lens)] or
            [np.zeros(0, np.int64)])
        keep = lens > 0
        got = expand_runs(starts[keep], lens[keep])
        np.testing.assert_array_equal(got, want.astype(np.int32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
@pytest.mark.parametrize("runs", [
    [(0, 1)],                                  # single-page single-run
    [(3, 4), (10, 2), (20, 1)],                # mixed lengths
    [(12, 1), (4, 1), (30, 1)],                # all singletons, unsorted
    [(0, 8), (16, 8)],                         # uniform long runs
])
def test_page_gather_runs_all_backends(dtype, runs):
    F, E = 40, 128
    key = jax.random.PRNGKey(3)
    if dtype == jnp.int32:
        frames = jax.random.randint(key, (F, E), 0, 1000)
    else:
        frames = jax.random.normal(key, (F, E), dtype)
    starts = np.array([s for s, _ in runs], np.int64)
    lens = np.array([l for _, l in runs], np.int64)
    ids = expand_runs(starts, lens)
    want = np.asarray(frames)[ids]
    for backend in BACKENDS:
        got = page_gather_runs(frames, starts, lens, backend=backend)
        np.testing.assert_array_equal(np.asarray(got), want,
                                      err_msg=f"backend={backend}")


def test_page_gather_runs_empty_and_zero_len():
    frames = jnp.arange(8 * 128, dtype=jnp.float32).reshape(8, 128)
    for backend in BACKENDS:
        got = page_gather_runs(frames, [], [], backend=backend)
        assert got.shape == (0, 128)
        # zero-length runs are filtered before the kernel sees them
        got = page_gather_runs(frames, [2, 5], [0, 3], backend=backend)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(frames)[5:8])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_cow_scatter_runs_all_backends(dtype):
    F, E = 32, 128
    runs = [(0, 3), (8, 1), (20, 4)]
    starts = np.array([s for s, _ in runs], np.int64)
    lens = np.array([l for _, l in runs], np.int64)
    ids = expand_runs(starts, lens)
    pages = jax.random.normal(jax.random.PRNGKey(1), (ids.size, E), dtype)
    want = None
    for backend in BACKENDS:
        frames = jax.random.normal(jax.random.PRNGKey(0), (F, E), dtype)
        got = np.asarray(cow_scatter_runs(frames, starts, lens, pages,
                                          backend=backend), np.float32)
        if want is None:
            base = np.asarray(frames, np.float32).copy()
            base[ids] = np.asarray(pages, np.float32)
            want = base
        np.testing.assert_array_equal(got, want, err_msg=f"backend={backend}")


def test_cow_scatter_runs_empty():
    frames = jnp.ones((4, 128), jnp.float32)
    for backend in BACKENDS:
        got = cow_scatter_runs(frames, [], [], jnp.zeros((0, 128)),
                               backend=backend)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(frames))


@pytest.mark.parametrize("shape", [(300,), (3, 129), (1, 1), (257,)])
def test_gather_assemble_matches_manual(shape):
    F, E = 16, 128
    frames = jax.random.normal(jax.random.PRNGKey(2), (F, E))
    size = int(np.prod(shape))
    n = -(-size // E)
    ids = np.random.default_rng(0).choice(F, n, replace=False).astype(np.int32)
    want = np.asarray(frames)[ids].reshape(-1)[:size].reshape(shape)
    for backend in BACKENDS:
        got = gather_assemble(frames, ids, shape, backend=backend)
        np.testing.assert_array_equal(np.asarray(got), want,
                                      err_msg=f"backend={backend}")


@pytest.mark.parametrize("shape", [(300,), (5, 70), (256,)])
def test_scatter_patch_matches_manual(shape):
    E = 128
    size = int(np.prod(shape))
    n = -(-size // E)
    rng = np.random.default_rng(1)
    t0 = rng.standard_normal(shape).astype(np.float32)
    ids = rng.choice(n, max(1, n // 2), replace=False).astype(np.int32)
    rows = rng.standard_normal((ids.size, E)).astype(np.float32)
    buf = np.zeros(n * E, np.float32)
    buf[:size] = t0.reshape(-1)
    buf.reshape(n, E)[ids] = rows
    want = buf[:size].reshape(shape)
    for backend in BACKENDS:
        got = scatter_patch(jnp.asarray(t0), ids, jnp.asarray(rows),
                            page_elems=E, backend=backend)
        np.testing.assert_array_equal(np.asarray(got), want,
                                      err_msg=f"backend={backend}")


def test_scatter_patch_empty_ids_is_identity():
    t = jnp.arange(10.0)
    got = scatter_patch(t, [], jnp.zeros((0, 128)), page_elems=128)
    assert got is t


def test_dispatch_auto_off_tpu_uses_jnp_and_meters():
    dispatch.reset_meters()
    frames = jnp.arange(4 * 128, dtype=jnp.float32).reshape(4, 128)
    page_gather_op(frames, jnp.array([1, 3], jnp.int32), backend="auto")
    meters = dispatch.kernel_meters()
    if dispatch.kernel_available():
        assert meters.get("kernel.page_gather.pallas", 0) == 1
    else:
        assert meters.get("kernel.page_gather.jnp", 0) == 1
    # drain folds into the caller's Counter and clears the module meter
    from collections import Counter
    sink = Counter()
    dispatch.drain_meters_into(sink)
    assert sum(sink.values()) >= 1
    assert not dispatch.kernel_meters()


def test_dispatch_kernel_off_tpu_warns_and_interprets():
    if dispatch.kernel_available():
        pytest.skip("compiled Pallas available; fallback path not taken")
    frames = jnp.arange(4 * 128, dtype=jnp.float32).reshape(4, 128)
    with pytest.warns(RuntimeWarning):
        dispatch._warned.clear()      # warn-once: re-arm for this test
        got = page_gather_op(frames, jnp.array([0], jnp.int32),
                             backend="kernel")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(frames[:1]))


def test_dispatch_rejects_unknown_backend():
    with pytest.raises(ValueError):
        dispatch.resolve_backend("cuda", kernel_name="page_gather")


def test_page_gather_runs_kernel_interpret_direct():
    # the raw run-table kernel (scalar-prefetched starts/lens/offs tables)
    F, E = 24, 128
    frames = jax.random.normal(jax.random.PRNGKey(9), (F, E))
    starts = np.array([2, 10, 20], np.int64)
    lens = np.array([4, 1, 3], np.int64)
    offs = np.concatenate([[0], np.cumsum(lens)[:-1]])
    got = _pgr_kernel(frames, jnp.asarray(starts, jnp.int32),
                      jnp.asarray(lens, jnp.int32),
                      jnp.asarray(offs, jnp.int32),
                      max_len=4, n_out=8, interpret=True)
    want = np.asarray(frames)[expand_runs(starts, lens)]
    np.testing.assert_array_equal(np.asarray(got), want)
