"""Public wrappers for page_gather: shape/dtype checking, the shared
backend dispatch (compiled Pallas when available, fused XLA otherwise —
see kernels/dispatch.py), the run-table (doorbell-shaped) variant, and the
fused gather->reassemble path the fault handler uses for tensor assembly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import dispatch
from repro.kernels.page_gather.kernel import page_gather as _kernel
from repro.kernels.page_gather.kernel import page_gather_runs as _kernel_runs
from repro.kernels.page_gather.ref import (expand_runs, page_gather_ref,
                                           page_gather_runs_ref)


@jax.jit
def _take_jit(frames, ids):
    return jnp.take(frames, ids, axis=0).reshape(ids.shape[0], -1)


def _page_elems(frames) -> int:
    if frames.ndim not in (2, 3):
        raise ValueError("frames must be (F, page_elems) or tiled "
                         f"(F, page_elems // 128, 128), got {frames.shape}")
    return int(np.prod(frames.shape[1:]))


@functools.partial(jax.jit, static_argnames=("size", "shape", "out_dtype"))
def _assemble_jit(frames, ids, *, size, shape, out_dtype):
    # one XLA fusion: gather -> flatten -> trim padding -> destination
    # layout; no intermediate page-list materialization
    flat = jnp.take(frames, ids, axis=0).reshape(-1)
    return jax.lax.slice(flat, (0,), (size,)).reshape(shape).astype(out_dtype)


def page_gather(frames, page_ids, *, backend: str = "auto"):
    """Gather pool frames by page id: frames (F, E) or the device pool's
    tiled (F, E // 128, 128); page_ids (n,) int32 -> (n, E).  ``backend``
    is resolved by ``kernels.dispatch`` (auto | kernel | interpret | jnp |
    ref)."""
    page_ids = jnp.asarray(page_ids, jnp.int32)
    E = _page_elems(frames)
    if page_ids.shape[0] == 0:
        return jnp.zeros((0, E), frames.dtype)
    impl, interpret = dispatch.resolve_backend(backend,
                                               kernel_name="page_gather")
    if impl == dispatch.IMPL_REF:
        return page_gather_ref(frames, page_ids)
    if impl == dispatch.IMPL_JNP:
        return _take_jit(frames, page_ids)
    return _kernel(frames, page_ids, interpret=interpret)


def page_gather_runs(frames, starts, lens, *, backend: str = "auto"):
    """Run-table gather — the doorbell-batch shape: each (start, len) pair
    is one contiguous frame extent (one SGE).  Returns (sum(lens), E),
    run-major.  Zero-length runs are filtered here; the kernels require
    ``lens >= 1``."""
    E = _page_elems(frames)
    starts_np = np.atleast_1d(np.asarray(starts, np.int64)).ravel()
    lens_np = np.atleast_1d(np.asarray(lens, np.int64)).ravel()
    keep = lens_np > 0
    starts_np, lens_np = starts_np[keep], lens_np[keep]
    if starts_np.size == 0:
        return jnp.zeros((0, E), frames.dtype)
    impl, interpret = dispatch.resolve_backend(backend,
                                               kernel_name="page_gather")
    if impl == dispatch.IMPL_REF:
        return page_gather_runs_ref(frames, starts_np, lens_np)
    if impl == dispatch.IMPL_JNP:
        return _take_jit(frames, jnp.asarray(expand_runs(starts_np, lens_np)))
    offs = np.concatenate([[0], np.cumsum(lens_np)[:-1]])
    return _kernel_runs(frames, jnp.asarray(starts_np, jnp.int32),
                        jnp.asarray(lens_np, jnp.int32),
                        jnp.asarray(offs, jnp.int32),
                        max_len=int(lens_np.max()), n_out=int(lens_np.sum()),
                        interpret=interpret)


def gather_assemble(frames, page_ids, shape, *, out_dtype=None,
                    backend: str = "auto"):
    """Fused gather->reassemble: fault pages land directly in the
    destination tensor layout (flatten, trim the last page's padding,
    reshape) with no intermediate page-list concatenate on the host."""
    page_ids = jnp.asarray(page_ids, jnp.int32)
    shape = tuple(int(s) for s in shape)
    size = int(np.prod(shape)) if shape else 1
    out_dtype = jnp.dtype(out_dtype or frames.dtype)
    impl, interpret = dispatch.resolve_backend(backend,
                                               kernel_name="page_gather")
    if impl in (dispatch.IMPL_KERNEL, dispatch.IMPL_INTERPRET):
        pages = _kernel(frames, page_ids, interpret=interpret)
        return pages.reshape(-1)[:size].reshape(shape).astype(out_dtype)
    if impl == dispatch.IMPL_REF:
        pages = page_gather_ref(frames, page_ids)
        return pages.reshape(-1)[:size].reshape(shape).astype(out_dtype)
    return _assemble_jit(frames, page_ids, size=size, shape=shape,
                         out_dtype=out_dtype)
