"""Pure-jnp oracle for cow_scatter."""
from __future__ import annotations

import jax.numpy as jnp


def cow_scatter_ref(frames, page_ids, pages):
    """frames: (F, E) or tiled (F, E // 128, 128); page_ids: (n,) unique
    int32; pages: (n, E).  Returns frames, in their layout, with the given
    pages written (COW commit)."""
    pages = pages.reshape((-1,) + frames.shape[1:])
    return frames.at[page_ids].set(pages.astype(frames.dtype))
