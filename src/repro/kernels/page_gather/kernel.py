"""page_gather Pallas TPU kernel — the MITOSIS fault handler's data plane.

The page table lives in SMEM via scalar prefetch (PrefetchScalarGridSpec),
so the BlockSpec index_map plays the role of the PTE walk: grid step i
copies pool frame pt[i] into output slot i, HBM->VMEM->HBM, one page per
grid step.  On real hardware the src pool can be a remote pod's HBM via
RDMA (`pltpu.make_async_remote_copy`); the on-chip structure is identical.

Pages are viewed as (rows, 128) tiles: 128-lane alignment is mandatory on
TPU, and page_elems is a multiple of 128 by construction (memory/pool.py).
On TPU, reshaping an (F, page_elems) array into those tiles is a relayout
copy of the whole array, so device pools hold their frames tiled already
(`tiled` passes a 3-D array through untouched) and a launch costs what it
moves, not the size of the pool.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128


def tiled(x):
    """(n, E) pages -> the kernels' (n, E // LANE, LANE) tile layout; an
    already-tiled 3-D array is returned as is."""
    if x.ndim == 3:
        return x
    n, E = x.shape
    assert E % LANE == 0, f"page_elems must be lane-aligned, got {E}"
    return x.reshape(n, E // LANE, LANE)


def _copy_kernel(pt_ref, src_ref, out_ref):
    out_ref[...] = src_ref[...]


def _copy_runs_kernel(starts_ref, lens_ref, offs_ref, src_ref, out_ref):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j < lens_ref[i])
    def _():
        out_ref[...] = src_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def page_gather(frames, page_ids, *, interpret: bool = True):
    """frames: (F, page_elems) or tiled (F, page_elems // 128, 128);
    page_ids: (n,) int32 -> (n, page_elems)."""
    src = tiled(frames)
    R = src.shape[1]
    n = page_ids.shape[0]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, R, LANE), lambda i, pt: (pt[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, R, LANE), lambda i, pt: (i, 0, 0)),
    )
    out = pl.pallas_call(
        _copy_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, R, LANE), frames.dtype),
        interpret=interpret,
    )(page_ids.astype(jnp.int32), src)
    return out.reshape(n, R * LANE)


@functools.partial(jax.jit, static_argnames=("max_len", "n_out", "interpret"))
def page_gather_runs(frames, starts, lens, offs, *, max_len: int, n_out: int,
                     interpret: bool = True):
    """Run-table (doorbell-batched) gather: the frame-id table arrives as
    maximal contiguous runs — exactly the SGE list PR 3's fault handler
    posts — instead of one id per page.

    frames: (F, page_elems) or tiled; starts/lens/offs: (num_runs,) int32 with
    ``lens >= 1`` (empty runs are filtered at the ops layer) and
    ``offs = exclusive cumsum(lens)``; ``n_out = sum(lens)`` pages out.

    Grid is (runs, max_len): step (i, j) copies pool frame
    ``starts[i] + j`` into output slot ``offs[i] + j`` while ``j`` is
    inside run i, so one scalar-prefetched table drives the whole extent
    run HBM->VMEM->HBM with no per-page host dispatch.  Steps past a
    run's end clamp their index map to the run's last block (already
    written at step ``lens[i]-1``) and skip the store.
    """
    src = tiled(frames)
    R = src.shape[1]
    num_runs = starts.shape[0]

    def _clamp(i, j, lens):
        return jnp.minimum(j, lens[i] - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(num_runs, max_len),
        in_specs=[
            pl.BlockSpec((1, R, LANE),
                         lambda i, j, starts, lens, offs:
                         (starts[i] + _clamp(i, j, lens), 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, R, LANE),
                               lambda i, j, starts, lens, offs:
                               (offs[i] + _clamp(i, j, lens), 0, 0)),
    )
    out = pl.pallas_call(
        _copy_runs_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_out, R, LANE), frames.dtype),
        interpret=interpret,
    )(starts.astype(jnp.int32), lens.astype(jnp.int32),
      offs.astype(jnp.int32), src)
    return out.reshape(n_out, R * LANE)
