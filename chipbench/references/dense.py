"""The dense attention block: one repeated unit of pre-norm attention and
MLP, as the repository's model runs it.

Per layer: RMSNorm with a (1 + gain), rotary q/k (halves rotated, whole
head), causal softmax attention with grouped kv heads, output projection,
RMSNorm, SwiGLU or tanh-GELU MLP; then the final norm and the head, tied
to the embedding or not.  Widths come from the configuration file through
its ``run`` block; the forward pass is float32 at ``highest`` precision,
with queries in blocks so that a long prompt fits beside the weights.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from chipbench.references import matmul
from repro.configs.base import GroupSpec, get_arch

Q_BLOCK = 512
BF16 = 2


@dataclasses.dataclass(frozen=True)
class Dims:
    """The numbers the harness, work counts and reference need."""
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    gated: bool
    tied: bool
    norm_eps: float
    rope_theta: float


def dims(conf: dict) -> Dims:
    run = conf["run"]
    w = run["widths"]
    return Dims(layers=int(conf[run["depth_key"]]), d=int(conf[w["d_model"]]),
                heads=int(conf[w["num_heads"]]),
                kv_heads=int(conf[w["num_kv_heads"]]) if "num_kv_heads" in w
                else int(run["num_kv_heads"]),
                head_dim=int(run["head_dim"]), d_ff=int(conf[w["d_ff"]]),
                vocab=int(conf[w["vocab_size"]]),
                gated=run["mlp"] == "gated_silu",
                tied=bool(run["tied_embeddings"]),
                norm_eps=float(run["norm_eps"]),
                rope_theta=float(run["rope_theta"]))


def arch(conf: dict):
    """The registry architecture at the file's depth, its widths checked."""
    run, dm = conf["run"], dims(conf)
    base = get_arch(run["arch"])
    if len(base.groups) != 1 or len(base.groups[0].unit) != 1:
        raise ValueError(f"{run['arch']}: expected one repeated block")
    cfg = dataclasses.replace(
        base, name=conf["name"],
        groups=(GroupSpec(unit=base.groups[0].unit, repeat=dm.layers),),
        param_dtype=run["param_dtype"], compute_dtype=run["compute_dtype"])
    want = {"d_model": dm.d, "num_heads": dm.heads, "num_kv_heads": dm.kv_heads,
            "head_dim": dm.head_dim, "d_ff": dm.d_ff, "vocab_size": dm.vocab,
            "mlp_gated": dm.gated, "tie_embeddings": dm.tied,
            "norm_eps": dm.norm_eps, "rope_theta": dm.rope_theta}
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        raise ValueError(f"{conf['name']}: registry {got} != file {want}")
    return cfg


def init_std(path: str, dm: Dims) -> float:
    """Init scale of one leaf, by its name in the program's layout."""
    leaf = path.rsplit("/", 1)[-1]
    table = {"wq": dm.d, "wk": dm.d, "wv": dm.d, "wo": dm.heads * dm.head_dim,
             "wi": dm.d, "wg": dm.d, "wd": dm.d_ff, "out": dm.d}
    if leaf == "tok":
        return 0.02
    if leaf == "scale":
        return 0.1
    if leaf not in table:
        raise KeyError(f"no init scale for leaf {path}")
    return 1.0 / math.sqrt(table[leaf])


# -- the forward pass ---------------------------------------------------------


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
        sc = matmul("qkgh,skh->kgqs", qb, k[:s + n], fp8) * hd ** -0.5
        mask = (s + jnp.arange(n))[:, None] >= jnp.arange(s + n)[None, :]
        w = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
        outs.append(matmul("kgqs,skh->qkgh", w, v[:s + n], fp8))
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
        q = _rope(matmul("td,dhk->thk", hn, p["attn"]["wq"], fp8), pos,
                  dm.rope_theta)
        k = _rope(matmul("td,dhk->thk", hn, p["attn"]["wk"], fp8), pos,
                  dm.rope_theta)
        v = matmul("td,dhk->thk", hn, p["attn"]["wv"], fp8)
        x = x + matmul("thk,hkd->td", _attention(q, k, v, fp8),
                       p["attn"]["wo"], fp8)
        hn = _rms(x, p["norm2"]["scale"], dm.norm_eps)
        up = matmul("td,df->tf", hn, p["mlp"]["wi"], fp8)
        if dm.gated:
            up = jax.nn.silu(matmul("td,df->tf", hn, p["mlp"]["wg"], fp8)) * up
        else:
            up = jax.nn.gelu(up, approximate=True)
        x = x + matmul("tf,fd->td", up, p["mlp"]["wd"], fp8)
    h = _rms(x[first:], params["final_norm"]["scale"].astype(jnp.float32),
             dm.norm_eps)
    if dm.tied:
        return matmul("td,vd->tv", h, params["embed"]["tok"], fp8)
    return matmul("td,dv->tv", h, params["embed"]["out"], fp8)


# -- required work ------------------------------------------------------------
# What the work needs, whatever implements it: no padding, no whole-pool
# copies, no recomputation (see work.py).


def layer_matmul_params(dm) -> int:
    attn = dm.d * dm.heads * dm.head_dim * 2 + dm.d * dm.kv_heads * dm.head_dim * 2
    return attn + (3 if dm.gated else 2) * dm.d * dm.d_ff


def head_params(dm) -> int:
    return dm.d * dm.vocab


def prefill_flops(dm, length: int) -> int:
    """One prompt of ``length`` tokens: every matmul per token, logits for
    the last position only (what prefill returns), and causal attention
    over the actual length (query i attends i + 1 keys, QK and PV)."""
    S = length
    dense = 2 * S * dm.layers * layer_matmul_params(dm)
    attn = dm.layers * 2 * dm.heads * dm.head_dim * S * (S + 1)
    return dense + 2 * head_params(dm) + attn


def decode_flops(dm, lengths) -> int:
    """One decode step over sequences whose new token sits at ``lengths``
    (positions already cached): per sequence every matmul and the head,
    and attention over length + 1 keys."""
    per_tok = 2 * (dm.layers * layer_matmul_params(dm) + head_params(dm))
    attn = sum(4 * dm.heads * dm.head_dim * (n + 1) for n in lengths)
    return len(lengths) * per_tok + dm.layers * attn


def paged_attention_work(dm, lengths, page_tokens: int,
                         kv_bytes: int = BF16) -> tuple:
    """One paged_attention call (one layer) for sequences at ``lengths``
    before the step, each attending length + 1 tokens: (flops, bytes).
    Bytes are the K and V pages the attended tokens cover, plus q and the
    output."""
    page = dm.kv_heads * page_tokens * dm.head_dim * kv_bytes
    nbytes, flops = 0, 0
    for n in lengths:
        eff = n + 1
        nbytes += 2 * (-(-eff // page_tokens)) * page
        flops += 4 * dm.heads * dm.head_dim * eff
    nbytes += 2 * len(lengths) * dm.heads * dm.head_dim * kv_bytes
    return flops, nbytes
