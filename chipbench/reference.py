"""The plain reference: the served model's forward pass in float32.

It imports nothing of the program.  It reads the weights the benchmark
made (``model.make_weights_fn``, made again from the seed after the window)
by their names in the parameter layout, and computes with every matmul at
``highest`` precision: embedding, then per layer RMSNorm with a (1 + gain),
rotary q/k (halves rotated, whole head), causal softmax attention with
grouped kv heads, output projection, RMSNorm, SwiGLU or tanh-GELU MLP, and
the final norm and head.  Queries are taken in blocks so that a long prompt
fits beside the weights.

``dot_fp8=True`` makes the control: the same pass with every matmul's
operands cast to float8_e4m3fn under a per-tensor absmax scale, the step
below the bfloat16 the configuration computes in.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _q8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(F8), s


def _einsum(spec, a, b, fp8):
    if not fp8:
        return jnp.einsum(spec, a, b, precision="highest",
                          preferred_element_type=jnp.float32)
    (qa, sa), (qb, sb) = _q8(a), _q8(b)
    return jnp.einsum(spec, qa, qb,
                      preferred_element_type=jnp.float32) * (sa * sb)


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + gain)


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv          # (T, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, fp8):
    """q (T, H, hd), k/v (T, K, hd): causal, queries in blocks."""
    T, H, hd = q.shape
    K = k.shape[1]
    q = q.reshape(T, K, H // K, hd)
    outs = []
    for s in range(0, T, Q_BLOCK):
        qb = q[s:s + Q_BLOCK]
        n = qb.shape[0]
        sc = _einsum("qkgh,skh->kgqs", qb, k[:s + n], fp8) * hd ** -0.5
        mask = (s + jnp.arange(n))[:, None] >= jnp.arange(s + n)[None, :]
        w = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
        outs.append(_einsum("kgqs,skh->qkgh", w, v[:s + n], fp8))
    return jnp.concatenate(outs).reshape(T, H, hd)


@functools.partial(jax.jit, static_argnames=("dm", "first", "fp8"))
def logits_at(params, tokens, *, dm, first: int, fp8: bool = False):
    """Logits (T - first, V) at positions first..T-1 of ``tokens`` (T,)."""
    T = tokens.shape[0]
    pos = jnp.arange(T)
    x = params["embed"]["tok"][tokens].astype(jnp.float32)
    blk = params["groups"][0]["blocks"][0]
    for li in range(dm.layers):
        p = jax.tree.map(lambda a: a[li].astype(jnp.float32), blk)
        hn = _rms(x, p["norm1"]["scale"], dm.norm_eps)
        q = _rope(_einsum("td,dhk->thk", hn, p["attn"]["wq"], fp8), pos,
                  dm.rope_theta)
        k = _rope(_einsum("td,dhk->thk", hn, p["attn"]["wk"], fp8), pos,
                  dm.rope_theta)
        v = _einsum("td,dhk->thk", hn, p["attn"]["wv"], fp8)
        x = x + _einsum("thk,hkd->td", _attention(q, k, v, fp8),
                        p["attn"]["wo"], fp8)
        hn = _rms(x, p["norm2"]["scale"], dm.norm_eps)
        up = _einsum("td,df->tf", hn, p["mlp"]["wi"], fp8)
        if dm.gated:
            up = jax.nn.silu(_einsum("td,df->tf", hn, p["mlp"]["wg"], fp8)) * up
        else:
            up = jax.nn.gelu(up, approximate=True)
        x = x + _einsum("tf,fd->td", up, p["mlp"]["wd"], fp8)
    h = _rms(x[first:], params["final_norm"]["scale"].astype(jnp.float32),
             dm.norm_eps)
    if dm.tied:
        return _einsum("td,vd->tv", h, params["embed"]["tok"], fp8)
    return _einsum("td,dv->tv", h, params["embed"]["out"], fp8)


def served_gaps(params, dm, prompt, served, fp8: bool = False):
    """For each served token: how far its reference logit lies below the
    reference's best at that position.  With ``fp8`` the tokens compared
    are the control's own first choices, not ``served``.  Returns a numpy
    array, one gap per served token."""
    toks = np.asarray(list(prompt) + list(served[:-1]), np.int32)
    first = len(prompt) - 1
    ref = logits_at(params, jnp.asarray(toks), dm=dm, first=first)
    if fp8:
        ctl = logits_at(params, jnp.asarray(toks), dm=dm, first=first,
                        fp8=True)
        pick = jnp.argmax(ctl, axis=-1)
    else:
        pick = jnp.asarray(np.asarray(served, np.int32))
    gap = jnp.max(ref, -1) - jnp.take_along_axis(ref, pick[:, None], 1)[:, 0]
    return np.asarray(gap)
