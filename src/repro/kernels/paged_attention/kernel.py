"""Paged decode attention (GQA) Pallas TPU kernel.

The serving hot loop: one query token per sequence attends over a KV cache
stored in pool pages (the COW-shared pages that remote fork gives children).
Flash-style online softmax across the page grid dimension; the per-sequence
page table is scalar-prefetched so BlockSpec index_maps route each grid step
to its pool frame — the same PTE-walk structure as page_gather.

Pages are head-major, (K, Tp, hd): a page's block is every kv head of Tp
tokens, and the block's trailing (Tp, hd) dims are whole array dims, which
is what Mosaic's tiling rule needs for any K.  Grid: (B, P) — batch x page;
each step walks the K heads of its page.  VMEM scratch carries the running
max / sum / accumulator across the page dimension (TPU grids execute
sequentially over the trailing axis, so scratch accumulation is sound).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_attn_kernel(lengths_ref, starts_ref, kt_ref, vt_ref, q_ref, k_ref,
                       v_ref, out_ref, m_ref, l_ref, acc_ref, *, tp, scale):
    b = pl.program_id(0)
    p = pl.program_id(1)
    n_pages = pl.num_programs(1)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    for h in range(q_ref.shape[1]):                      # kv heads, unrolled
        q = q_ref[0, h].astype(jnp.float32)              # (G, hd)
        k = k_ref[0, h].astype(jnp.float32)              # (Tp, hd)
        v = v_ref[0, h].astype(jnp.float32)              # (Tp, hd)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        # mask tokens outside [start, length) — start>0 implements sliding
        # windows
        token_idx = p * tp + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where((token_idx < lengths_ref[b])
                      & (token_idx >= starts_ref[b]), s, NEG_INF)

        m_prev = m_ref[h]                                # (G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        pexp = jnp.exp(s - m_new)                        # (G, Tp)
        l_ref[h] = alpha * l_ref[h] + jnp.sum(pexp, axis=1, keepdims=True)
        acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
            pexp, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[h] = m_new

    @pl.when(p == n_pages - 1)
    def _finish():
        out_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                      ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q, kv_pages_k, kv_pages_v, page_table, lengths, *,
                    v_page_table=None, starts=None, interpret: bool = True):
    """q: (B, K, G, hd); kv pages: (F, K, Tp, hd); page_table: (B, P) int32
    (for K; V uses v_page_table if given, else the same table);
    lengths: (B,); starts: optional (B,) window lower bound.
    Returns (B, K, G, hd)."""
    B, K, G, hd = q.shape
    F, _, Tp, _ = kv_pages_k.shape
    P = page_table.shape[1]
    scale = hd ** -0.5
    if starts is None:
        starts = jnp.zeros_like(lengths)
    if v_page_table is None:
        v_page_table = page_table

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, P),
        in_specs=[
            pl.BlockSpec((1, K, G, hd),
                         lambda b, p, ln, st, kt, vt: (b, 0, 0, 0)),
            pl.BlockSpec((1, K, Tp, hd),
                         lambda b, p, ln, st, kt, vt: (kt[b, p], 0, 0, 0)),
            pl.BlockSpec((1, K, Tp, hd),
                         lambda b, p, ln, st, kt, vt: (vt[b, p], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, K, G, hd),
                               lambda b, p, ln, st, kt, vt: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((K, G, 1), jnp.float32),
            pltpu.VMEM((K, G, 1), jnp.float32),
            pltpu.VMEM((K, G, hd), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_attn_kernel, tp=Tp, scale=scale)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, hd), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(lengths.astype(jnp.int32), starts.astype(jnp.int32),
      page_table.astype(jnp.int32), v_page_table.astype(jnp.int32),
      q, kv_pages_k, kv_pages_v)
