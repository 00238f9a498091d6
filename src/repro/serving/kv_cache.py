"""Paged KV cache on top of the MITOSIS PagePool.

One page = `page_tokens` KV slots of one layer, for K or V, stored
head-major as (K heads, page_tokens, head_dim) — the block layout the
paged_attention kernel reads (kernels/paged_attention).  Sequences hold
per-layer page tables; `fork_sequence` shares pages copy-on-write with refcounts — the serving-side realization of the paper's
zero-serialization state transfer (children fork the parent's prefix pages
and append privately).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.memory.pool import PagePool


@dataclasses.dataclass
class SeqKV:
    seq_id: int
    length: int
    # page tables: (L, P) int32 frame ids for K and V
    k_pages: np.ndarray
    v_pages: np.ndarray
    # copy-on-write: pages shared with an ancestor are read-only
    shared_mask: np.ndarray       # (P,) bool — True = shared (not writable)


@functools.partial(jax.jit, static_argnames=("page_tokens", "dtype"))
def _prefill_pages(k, v, *, page_tokens, dtype):
    """Token-major k/v (L, S, K, hd) -> head-major pages
    (2 * L * ncols, K * page_tokens * hd) in ``dtype``: K's pages over V's,
    each layer-major, the order of ``concat(k_pages.ravel(),
    v_pages.ravel())``; the last column is zero-padded."""
    L, S, K, hd = k.shape
    ncols = -(-S // page_tokens)
    kv = jnp.stack([k, v])
    kv = jnp.pad(kv, ((0, 0), (0, 0), (0, ncols * page_tokens - S),
                      (0, 0), (0, 0)))
    kv = kv.reshape(2, L, ncols, page_tokens, K, hd).transpose(0, 1, 2, 4, 3, 5)
    return kv.reshape(2 * L * ncols, K * page_tokens * hd).astype(dtype)


class PagedKV:
    def __init__(self, num_layers: int, kv_heads: int, head_dim: int,
                 page_tokens: int = 16, dtype=jnp.bfloat16,
                 pool: Optional[PagePool] = None):
        self.L = num_layers
        self.K = kv_heads
        self.hd = head_dim
        self.Tp = page_tokens
        self.dtype = jnp.dtype(dtype)
        self.page_elems = page_tokens * kv_heads * head_dim
        self.pool = pool or PagePool(page_elems=self.page_elems)
        assert self.pool.page_elems == self.page_elems
        self.refcount: Dict[int, int] = {}
        self.seqs: Dict[int, SeqKV] = {}
        self._next = 0

    # -- frames view for the attention kernel ---------------------------------

    def frames_view(self):
        with tracing.span("kv.frames_view"):
            f = self.pool.frames_array(self.dtype)
            return f.reshape(f.shape[0], self.K, self.Tp, self.hd)

    # -- sequence lifecycle ----------------------------------------------------

    def new_seq(self) -> int:
        sid = self._next
        self._next += 1
        self.seqs[sid] = SeqKV(sid, 0,
                               np.zeros((self.L, 0), np.int32),
                               np.zeros((self.L, 0), np.int32),
                               np.zeros((0,), bool))
        return sid

    def _alloc_column(self, seq: SeqKV) -> None:
        """Append one page per layer for K and V."""
        kf = self.pool.alloc(self.dtype, self.L)
        vf = self.pool.alloc(self.dtype, self.L)
        for f in list(kf) + list(vf):
            self.refcount[int(f)] = 1
        seq.k_pages = np.concatenate([seq.k_pages, kf[:, None]], axis=1)
        seq.v_pages = np.concatenate([seq.v_pages, vf[:, None]], axis=1)
        seq.shared_mask = np.concatenate([seq.shared_mask, [False]])

    def _cow_column(self, seq: SeqKV, col: int) -> None:
        """Privatize a shared page column before writing (COW)."""
        old_k, old_v = seq.k_pages[:, col].copy(), seq.v_pages[:, col].copy()
        kf = self.pool.alloc(self.dtype, self.L)
        vf = self.pool.alloc(self.dtype, self.L)
        self.pool.write_pages(self.dtype, kf,
                              self.pool.read_pages(self.dtype, old_k))
        self.pool.write_pages(self.dtype, vf,
                              self.pool.read_pages(self.dtype, old_v))
        for f in list(kf) + list(vf):
            self.refcount[int(f)] = 1
        for f in list(old_k) + list(old_v):
            self._unref(int(f))
        seq.k_pages[:, col] = kf
        seq.v_pages[:, col] = vf
        seq.shared_mask[col] = False

    def ensure_writable_slot(self, sid: int) -> tuple:
        """Returns (col, slot) where the next token goes; allocates/COWs."""
        seq = self.seqs[sid]
        col, slot = divmod(seq.length, self.Tp)
        if col >= seq.k_pages.shape[1]:
            self._alloc_column(seq)
        elif seq.shared_mask[col]:
            self._cow_column(seq, col)
        return col, slot

    def append_token(self, sid: int, k_rows, v_rows) -> None:
        """k_rows/v_rows: (L, K, hd) for the new token."""
        seq = self.seqs[sid]
        col, slot = self.ensure_writable_slot(sid)
        slots = [slot] * self.L
        self.pool.write_rows(self.dtype, seq.k_pages[:, col], slots, k_rows)
        self.pool.write_rows(self.dtype, seq.v_pages[:, col], slots, v_rows)
        seq.length += 1

    def write_prefill(self, sid: int, k, v) -> None:
        """k/v: (L, S, K, hd) — bulk-write a prefilled prefix: one
        allocation each for K and V, one pool commit for both."""
        with tracing.span("kv.write_prefill"):
            L, S = k.shape[0], k.shape[1]
            seq = self.seqs[sid]
            assert seq.length == 0
            ncols = -(-S // self.Tp)
            kf = self.pool.alloc(self.dtype, L * ncols)
            vf = self.pool.alloc(self.dtype, L * ncols)
            frames = np.concatenate([kf, vf])
            self.refcount.update(dict.fromkeys(frames.tolist(), 1))
            seq.k_pages = kf.reshape(L, ncols)
            seq.v_pages = vf.reshape(L, ncols)
            seq.shared_mask = np.zeros(ncols, bool)
            self.pool.write_pages(
                self.dtype, frames,
                _prefill_pages(k, v, page_tokens=self.Tp, dtype=self.dtype))
            seq.length = S

    # -- fork (the paper's state transfer) ---------------------------------------

    def fork_sequence(self, sid: int) -> int:
        """COW-fork: child shares every existing page read-only."""
        src = self.seqs[sid]
        child = self.new_seq()
        dst = self.seqs[child]
        dst.length = src.length
        dst.k_pages = src.k_pages.copy()
        dst.v_pages = src.v_pages.copy()
        dst.shared_mask = np.ones(src.k_pages.shape[1], bool)
        src.shared_mask = np.ones(src.k_pages.shape[1], bool)  # parent too
        for f in list(src.k_pages.ravel()) + list(src.v_pages.ravel()):
            self.refcount[int(f)] = self.refcount.get(int(f), 1) + 1
        return child

    def _unref(self, frame: int) -> None:
        self.refcount[frame] = self.refcount.get(frame, 1) - 1
        if self.refcount[frame] <= 0:
            self.pool.free(self.dtype, [frame])
            del self.refcount[frame]

    def free_seq(self, sid: int) -> None:
        seq = self.seqs.pop(sid, None)
        if seq is None:
            return
        for f in list(seq.k_pages.ravel()) + list(seq.v_pages.ravel()):
            self._unref(int(f))

    # -- batched views for attention ----------------------------------------------

    def batch_tables(self, sids: List[int]):
        """Pad page tables to a common length: returns (k_pt, v_pt, lengths)
        with shape (B, L, P)."""
        P = max(self.seqs[s].k_pages.shape[1] for s in sids)
        B = len(sids)
        k_pt = np.zeros((B, self.L, P), np.int32)
        v_pt = np.zeros((B, self.L, P), np.int32)
        lens = np.zeros((B,), np.int32)
        for i, s in enumerate(sids):
            seq = self.seqs[s]
            p = seq.k_pages.shape[1]
            k_pt[i, :, :p] = seq.k_pages
            v_pt[i, :, :p] = seq.v_pages
            lens[i] = seq.length
        return jnp.asarray(k_pt), jnp.asarray(v_pt), jnp.asarray(lens)

    def bytes_in_use(self) -> int:
        return self.pool.bytes_allocated()
