"""cow_scatter Pallas TPU kernel — the COW commit path.

Writes freshly-COW'd pages into their allocated pool frames in place
(input/output aliasing), with the frame ids scalar-prefetched so the output
BlockSpec index_map routes each page to its frame.  Inverse index map of
page_gather; frames not addressed by `page_ids` are untouched (aliased).

`page_ids` must be unique (each dirty page gets a fresh frame from the
allocator, so duplicates cannot occur in the fork runtime).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.page_gather.kernel import LANE, tiled


def _scatter_kernel(pt_ref, pages_ref, frames_ref, out_ref):
    out_ref[...] = pages_ref[...]


def _scatter_runs_kernel(starts_ref, lens_ref, offs_ref, pages_ref,
                         frames_ref, out_ref):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j < lens_ref[i])
    def _():
        out_ref[...] = pages_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(0,))
def cow_scatter(frames, page_ids, pages, *, interpret: bool = True):
    """frames: (F, E) pool, or tiled (F, E // 128, 128) — returned in the
    layout given; page_ids: (n,) int32 unique; pages: (n, E)."""
    dst = tiled(frames)
    F, R, _ = dst.shape
    n = page_ids.shape[0]
    src = tiled(pages).astype(frames.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, R, LANE), lambda i, pt: (i, 0, 0)),      # pages
            pl.BlockSpec((1, R, LANE), lambda i, pt: (pt[i], 0, 0)),  # frames
        ],
        out_specs=pl.BlockSpec((1, R, LANE), lambda i, pt: (pt[i], 0, 0)),
    )
    out = pl.pallas_call(
        _scatter_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((F, R, LANE), frames.dtype),
        input_output_aliases={2: 0},      # alias frames input -> output
        interpret=interpret,
    )(page_ids.astype(jnp.int32), src, dst)
    return out.reshape(frames.shape)


@functools.partial(jax.jit, static_argnames=("max_len", "interpret"),
                   donate_argnums=(0,))
def cow_scatter_runs(frames, starts, lens, offs, pages, *, max_len: int,
                     interpret: bool = True):
    """Run-table (doorbell-batched) COW commit: freshly-COW'd pages land in
    their allocated frame extents as one fused scatter per run table — the
    inverse of :func:`page_gather_runs`.

    frames: (F, E) pool or tiled, returned in the layout given;
    starts/lens/offs: (num_runs,) int32 describing contiguous destination
    extents (``lens >= 1``, runs must not overlap —
    each dirty page gets a fresh frame from the allocator); pages:
    (sum(lens), E) payload, run-major.  Grid step (i, j) writes payload row
    ``offs[i] + j`` into frame ``starts[i] + j``; steps past a run's end
    clamp to the run's last block (just written) and skip the store, so the
    aliased pool content outside the runs is untouched.
    """
    dst = tiled(frames)
    F, R, _ = dst.shape
    num_runs = starts.shape[0]
    src = tiled(pages).astype(frames.dtype)

    def _clamp(i, j, lens):
        return jnp.minimum(j, lens[i] - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(num_runs, max_len),
        in_specs=[
            pl.BlockSpec((1, R, LANE),
                         lambda i, j, starts, lens, offs:
                         (offs[i] + _clamp(i, j, lens), 0, 0)),      # pages
            pl.BlockSpec((1, R, LANE),
                         lambda i, j, starts, lens, offs:
                         (starts[i] + _clamp(i, j, lens), 0, 0)),    # frames
        ],
        out_specs=pl.BlockSpec((1, R, LANE),
                               lambda i, j, starts, lens, offs:
                               (starts[i] + _clamp(i, j, lens), 0, 0)),
    )
    out = pl.pallas_call(
        _scatter_runs_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((F, R, LANE), frames.dtype),
        input_output_aliases={4: 0},      # alias frames input -> output
        interpret=interpret,
    )(starts.astype(jnp.int32), lens.astype(jnp.int32),
      offs.astype(jnp.int32), src, dst)
    return out.reshape(frames.shape)
