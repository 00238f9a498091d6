"""Public wrapper for paged decode attention: shape checks and the shared
backend dispatch (kernels/dispatch.py), which also meters the choice."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import dispatch
from repro.kernels.paged_attention.kernel import paged_attention as _kernel
from repro.kernels.paged_attention.ref import paged_attention_ref

_ref_jit = jax.jit(paged_attention_ref)


def paged_attention(q, kv_pages_k, kv_pages_v, page_table, lengths, *,
                    v_page_table=None, starts=None, backend: str = "auto"):
    """Decode attention over paged KV (GQA).

    q: (B, K, G, hd) — G = query heads per kv head.
    kv_pages_*: (F, K, Tp, hd) head-major pool frames; page_table: (B, P);
    lengths: (B,); starts: optional (B,) lower bound (sliding windows).
    ``backend`` is resolved by ``kernels.dispatch`` (auto | kernel |
    interpret | jnp | ref).
    """
    q = jnp.asarray(q)
    if q.ndim != 4:
        raise ValueError(f"q must be (B,K,G,hd), got {q.shape}")
    if kv_pages_k.shape != kv_pages_v.shape:
        raise ValueError("k/v page pools must match")
    impl, interpret = dispatch.resolve_backend(backend,
                                               kernel_name="paged_attention")
    if impl == dispatch.IMPL_REF:
        return paged_attention_ref(q, kv_pages_k, kv_pages_v, page_table,
                                   lengths, starts, v_page_table)
    if impl == dispatch.IMPL_JNP:
        return _ref_jit(q, kv_pages_k, kv_pages_v, page_table, lengths,
                        starts, v_page_table)
    return _kernel(q, kv_pages_k, kv_pages_v, page_table, lengths,
                   v_page_table=v_page_table, starts=starts,
                   interpret=interpret)
