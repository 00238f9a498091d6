"""Public wrappers for cow_scatter: backend dispatch (kernels/dispatch.py),
the run-table (extent-run) commit variant, and the fused tensor-patch path
used by incremental reassembly."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import dispatch
from repro.kernels.cow_scatter.kernel import cow_scatter as _kernel
from repro.kernels.cow_scatter.kernel import cow_scatter_runs as _kernel_runs
from repro.kernels.cow_scatter.ref import cow_scatter_ref
from repro.kernels.page_gather.ref import expand_runs


_set_jit = jax.jit(cow_scatter_ref)


@functools.partial(jax.jit, static_argnames=("npages", "page_elems"))
def _patch_jit(t, ids, rows, *, npages, page_elems):
    # one XLA fusion: flatten -> pad to the page grid -> scatter the
    # changed pages -> trim -> original layout
    size = t.size
    flat = t.reshape(-1).astype(rows.dtype)
    pad = npages * page_elems - size
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros(pad, rows.dtype)])
    paged = flat.reshape(npages, page_elems).at[ids].set(rows)
    return (jax.lax.slice(paged.reshape(-1), (0,), (size,))
            .reshape(t.shape).astype(t.dtype))


def cow_scatter(frames, page_ids, pages, *, backend: str = "auto"):
    """Commit COW pages into pool frames: frames (F, E) or the device
    pool's tiled (F, E // 128, 128); page_ids (n,) unique int32; pages
    (n, E) -> updated frames, in the layout given."""
    page_ids = jnp.asarray(page_ids, jnp.int32)
    if page_ids.shape[0] == 0:
        return frames
    impl, interpret = dispatch.resolve_backend(backend,
                                               kernel_name="cow_scatter")
    if impl == dispatch.IMPL_REF:
        return cow_scatter_ref(frames, page_ids, pages)
    if impl == dispatch.IMPL_JNP:
        return _set_jit(frames, page_ids, pages)
    return _kernel(frames, page_ids, pages, interpret=interpret)


def cow_scatter_runs(frames, starts, lens, pages, *, backend: str = "auto"):
    """Run-table COW commit: each (start, len) pair is one contiguous
    destination extent; pages is the run-major payload (sum(lens), E).
    Runs must not overlap (fresh frames from the allocator)."""
    starts_np = np.atleast_1d(np.asarray(starts, np.int64)).ravel()
    lens_np = np.atleast_1d(np.asarray(lens, np.int64)).ravel()
    keep = lens_np > 0
    starts_np, lens_np = starts_np[keep], lens_np[keep]
    if starts_np.size == 0:
        return frames
    impl, interpret = dispatch.resolve_backend(backend,
                                               kernel_name="cow_scatter")
    if impl == dispatch.IMPL_REF:
        return cow_scatter_ref(frames,
                               jnp.asarray(expand_runs(starts_np, lens_np)),
                               pages)
    if impl == dispatch.IMPL_JNP:
        return _set_jit(frames, jnp.asarray(expand_runs(starts_np, lens_np)),
                        pages)
    offs = np.concatenate([[0], np.cumsum(lens_np)[:-1]])
    return _kernel_runs(frames, jnp.asarray(starts_np, jnp.int32),
                        jnp.asarray(lens_np, jnp.int32),
                        jnp.asarray(offs, jnp.int32), pages,
                        max_len=int(lens_np.max()), interpret=interpret)


def scatter_patch(t, page_ids, rows, *, page_elems: int,
                  backend: str = "auto"):
    """Patch changed pages into an already-assembled tensor ``t``: the
    incremental-reassembly path.  ``rows`` is (n, page_elems) page payload;
    page ``p`` covers flat elements ``[p*page_elems, (p+1)*page_elems)`` of
    ``t`` (the final page's padding is trimmed).  Fused on device; never
    re-gathers unchanged pages."""
    page_ids = jnp.asarray(page_ids, jnp.int32)
    if page_ids.shape[0] == 0:
        return t
    size = int(np.prod(t.shape)) if t.shape else 1
    npages = -(-size // page_elems)
    impl, interpret = dispatch.resolve_backend(backend,
                                               kernel_name="cow_scatter")
    if impl == dispatch.IMPL_REF:
        flat = np.asarray(t, jnp.dtype(rows.dtype)).reshape(-1)
        buf = np.zeros(npages * page_elems, flat.dtype)
        buf[:size] = flat
        buf.reshape(npages, page_elems)[np.asarray(page_ids)] = \
            np.asarray(rows)
        return jnp.asarray(buf[:size].reshape(t.shape).astype(t.dtype))
    if impl in (dispatch.IMPL_KERNEL, dispatch.IMPL_INTERPRET):
        flat = t.reshape(-1).astype(rows.dtype)
        pad = npages * page_elems - size
        if pad:
            flat = jnp.concatenate([flat, jnp.zeros(pad, rows.dtype)])
        paged = _kernel(flat.reshape(npages, page_elems), page_ids, rows,
                        interpret=interpret)
        return (paged.reshape(-1)[:size].reshape(t.shape).astype(t.dtype))
    return _patch_jit(t, page_ids, rows, npages=npages,
                      page_elems=page_elems)
