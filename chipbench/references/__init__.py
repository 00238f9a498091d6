"""One module per block layout: all the harness knows of a block (its
widths, init scales and work counts) beside the block's plain float32
reference, which decides ``correct``; and the precision the references
share.

A configuration file names its module in ``run.reference``; the harness
loads ``references/<name>.py`` by that name alone (``spec.reference_module``)
and calls nothing of a block but through it.  A module gives:

* ``dims(conf)``: the numbers its other functions need, as a hashable
  object with at least ``vocab`` (the range of token ids);
* ``arch(conf)``: the repository's registry architecture at the file's
  depth and dtypes, its widths checked against the file;
* ``init_std(path, dims)``: the init scale of the parameter leaf at
  ``path`` (``/``-joined keys of the program's layout);
* ``logits_at(params, tokens, *, dm, first, fp8=False)``: the forward pass
  in float32, logits at positions ``first``.. of ``tokens``, every matmul
  through ``matmul`` below;
* ``prefill_flops(dm, length)``, ``decode_flops(dm, lengths)`` and
  ``paged_attention_work(dm, lengths, page_tokens)``: the work a prefill, a
  decode step and one layer's ``paged_attention`` call require.

The reference imports nothing of the program's model: it reads the weights
the benchmark made from the seed by their names in the parameter layout.
"""
from __future__ import annotations

import jax.numpy as jnp

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _q8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(F8), s


def matmul(spec, a, b, fp8):
    """``einsum(spec, a, b)`` at ``highest`` precision into float32; with
    ``fp8`` the control: both operands cast to float8_e4m3fn under a
    per-tensor absmax scale, the step below the bfloat16 the configurations
    compute in."""
    if not fp8:
        return jnp.einsum(spec, a, b, precision="highest",
                          preferred_element_type=jnp.float32)
    (qa, sa), (qb, sb) = _q8(a), _q8(b)
    return jnp.einsum(spec, qa, qb,
                      preferred_element_type=jnp.float32) * (sa * sb)
