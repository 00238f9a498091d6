"""Pure-jnp oracle for paged decode attention (GQA)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def paged_attention_ref(q, kv_pages_k, kv_pages_v, page_table, lengths,
                        starts=None, v_page_table=None):
    """q: (B, K, G, hd); kv pages: (F, K, Tp, hd) head-major;
    page_table: (B, P) int32; lengths: (B,) int32; starts: optional (B,)
    window lower bound.  Returns (B, K, G, hd).

    Slot t of sequence b lives at page page_table[b, t // Tp], row t % Tp.
    """
    B, K, G, hd = q.shape
    F, _, Tp, _ = kv_pages_k.shape
    P = page_table.shape[1]
    if starts is None:
        starts = jnp.zeros_like(lengths)
    if v_page_table is None:
        v_page_table = page_table

    def seqs(pages, table):               # (B, P, K, Tp, hd) -> (B, K, S, hd)
        x = jnp.take(pages, table, axis=0).transpose(0, 2, 1, 3, 4)
        return x.reshape(B, K, P * Tp, hd).astype(jnp.float32)

    k, v = seqs(kv_pages_k, page_table), seqs(kv_pages_v, v_page_table)
    scores = jnp.einsum("bkgh,bksh->bkgs", q.astype(jnp.float32),
                        k) * (hd ** -0.5)
    t = jnp.arange(P * Tp)[None, :]
    mask = (t < lengths[:, None]) & (t >= starts[:, None])      # (B, S)
    scores = jnp.where(mask[:, None, None, :], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bkgs,bksh->bkgh", w, v).astype(q.dtype)
