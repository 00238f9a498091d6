"""Serving engine: continuous batching over a paged KV cache, with
fork-based prefix sharing (the MITOSIS state-transfer path).

Supports the dense/MoE attention architectures through a paged decode
forward built from the same layer primitives as the training model (SSM
archs serve through lm.decode_step's O(1) recurrent states instead — their
state rides in the fork descriptor like CPU registers).

The decode attention runs through kernels/paged_attention (Pallas on TPU,
oracle elsewhere), reading KV directly from pool frames — children created
by `fork_request` attend over the parent's pages with zero copies.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.configs.base import ArchConfig, AttnSpec
from repro.kernels.paged_attention.ops import paged_attention
from repro.models import layers as L
from repro.models import lm
from repro.models import moe as MOE
from repro.serving.kv_cache import PagedKV
from repro.serving.sampling import sample


@dataclasses.dataclass
class Request:
    req_id: int
    prompt: List[int]
    max_tokens: int
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    seq_id: Optional[int] = None
    done: bool = False
    submitted: Optional[float] = None   # perf_counter at submit


class ServingEngine:
    def __init__(self, cfg: ArchConfig, params, *, page_tokens: int = 16,
                 backend: str = "auto", eos_id: int = -1):
        self.cfg = cfg
        specs = [s for s in cfg.block_specs() if isinstance(s, AttnSpec)]
        if len(specs) != cfg.num_layers:
            raise ValueError("paged engine supports attention archs; "
                             "use the recurrent-state engine for SSM archs")
        self.specs = list(cfg.block_specs())
        self.params = params
        self.kv = PagedKV(cfg.num_layers, cfg.num_kv_heads, cfg.head_dim,
                          page_tokens=page_tokens,
                          dtype=jnp.dtype(cfg.compute_dtype))
        self.backend = backend
        self.eos_id = eos_id
        self.requests: Dict[int, Request] = {}
        self.active: List[int] = []
        self.waiting: List[int] = []
        self._rid = 0

    def _layer_params(self):
        """Yield (spec, params) per layer in execution order.  Stacked
        params are sliced as each layer runs: holding an unstacked copy
        would double the model's device memory."""
        for g, gp in zip(self.cfg.groups, self.params["groups"]):
            for r in range(g.repeat):
                for bi, spec in enumerate(g.unit):
                    bp = gp["blocks"][bi]
                    if getattr(spec, "shared", False):
                        yield spec, bp
                    else:
                        yield spec, jax.tree.map(lambda x: x[r], bp)

    # -- request lifecycle -----------------------------------------------------

    def submit(self, prompt: List[int], max_tokens: int = 16) -> int:
        rid = self._rid
        self._rid += 1
        self.requests[rid] = Request(rid, list(prompt), max_tokens,
                                     submitted=time.perf_counter())
        self.waiting.append(rid)
        return rid

    def fork_request(self, src_rid: int, max_tokens: int = 16) -> int:
        """Fork a running request: shares its KV prefix pages COW."""
        src = self.requests[src_rid]
        rid = self._rid
        self._rid += 1
        r = Request(rid, list(src.prompt) + list(src.out_tokens), max_tokens)
        r.seq_id = self.kv.fork_sequence(src.seq_id)
        self.requests[rid] = r
        self.active.append(rid)
        return rid

    # -- model internals ---------------------------------------------------------

    def _prefill(self, req: Request) -> None:
        with tracing.span("engine.prefill", req.req_id):
            toks = jnp.asarray(req.prompt, jnp.int32)[None, :]
            cache_len = ((len(req.prompt) + self.kv.Tp - 1) // self.kv.Tp) * self.kv.Tp
            # no sync at its end: the forward's device time overlaps the
            # dispatches that follow, and kv.write_prefill's copy waits for it
            with tracing.span("lm.prefill"):
                logits, caches = lm.prefill(self.params, self.cfg, toks,
                                            cache_len)
            req.seq_id = self.kv.new_seq()
            # flatten the grouped caches into (L, S, K, hd)
            ks, vs = [], []
            for g, gc in zip(self.cfg.groups, caches["groups"]):
                for r in range(g.repeat):               # execution order: repeat
                    for bi, spec in enumerate(g.unit):  # outer, unit inner
                        c = gc["blocks"][bi]
                        ks.append(c["k"][r, 0])
                        vs.append(c["v"][r, 0])
            k = jnp.stack(ks)[:, :len(req.prompt)]
            v = jnp.stack(vs)[:, :len(req.prompt)]
            self.kv.write_prefill(req.seq_id, k, v)
            with tracing.span("engine.first_token"):
                tok = int(jnp.argmax(logits[0, -1] if logits.ndim == 3
                                     else logits[0]))
            req.out_tokens.append(tok)

    def _decode_batch(self, rids: List[int], key) -> None:
        with tracing.span("engine.decode", tuple(rids)):
            B = len(rids)
            cfg = self.cfg
            reqs = [self.requests[r] for r in rids]
            sids = [r.seq_id for r in reqs]
            toks = jnp.asarray([(r.out_tokens[-1] if r.out_tokens else r.prompt[-1])
                                for r in reqs], jnp.int32)
            pos = jnp.asarray([self.kv.seqs[s].length for s in sids], jnp.int32)
            dt = jnp.dtype(cfg.compute_dtype)

            # reserve the slot for the incoming token (alloc/COW before write)
            with tracing.span("kv.tables"):
                for s in sids:
                    self.kv.ensure_writable_slot(s)
                k_pt, v_pt, lens = self.kv.batch_tables(sids)

            # the forward, dispatched op by op; kv.* spans inside it
            with tracing.span("lm.decode"):
                h = L.embed_tokens(self.params["embed"], cfg, toks[:, None], dt)
                for li, (spec, bp) in enumerate(self._layer_params()):
                    hn = L.rms_norm(h, bp["norm1"]["scale"], cfg.norm_eps)
                    q, k1, v1 = L._project_qkv(bp["attn"], hn, spec, cfg,
                                               pos[:, None])
                    # write this token's K/V into the reserved slot, then attend
                    self._write_token(sids, li, k1[:, 0], v1[:, 0])
                    frames = self.kv.frames_view()
                    G = cfg.num_heads // cfg.num_kv_heads
                    qh = q[:, 0].reshape(B, cfg.num_kv_heads, G, cfg.head_dim)
                    eff = lens + 1
                    starts = (jnp.maximum(eff - spec.window, 0)
                              if spec.window is not None else None)
                    att = paged_attention(qh, frames, frames, k_pt[:, li], eff,
                                          v_page_table=v_pt[:, li], starts=starts,
                                          backend=self.backend)
                    a = att.reshape(B, 1, cfg.num_heads, cfg.head_dim)
                    y = jnp.einsum("bshk,hkd->bsd", a, bp["attn"]["wo"].astype(dt))
                    h = h + y
                    if "mlp" in bp or "moe" in bp:
                        hn2 = L.rms_norm(h, bp["norm2"]["scale"], cfg.norm_eps)
                        if "moe" in bp:
                            h = h + MOE.moe_mlp(bp["moe"], hn2, cfg)
                        else:
                            h = h + L.mlp(bp["mlp"], hn2, cfg.mlp_gated)
                h = L.rms_norm(h, self.params["final_norm"]["scale"], cfg.norm_eps)
                logits = L.output_logits(self.params["embed"], cfg, h)[:, 0]
            with tracing.span("engine.sample"):
                toks_new = sample(logits, key)
                for i, (r, s) in enumerate(zip(reqs, sids)):
                    self.kv.seqs[s].length += 1
                    t = int(toks_new[i])
                    r.out_tokens.append(t)
                    if t == self.eos_id or len(r.out_tokens) >= r.max_tokens:
                        r.done = True

    def _write_token(self, sids, layer, k_rows, v_rows) -> None:
        """k_rows/v_rows: (B, K, hd) for one layer at each seq's current pos."""
        with tracing.span("kv.write_token"):
            kv = self.kv
            kf, vf, slots = [], [], []
            for s in sids:
                seq = kv.seqs[s]
                col, slot = divmod(seq.length, kv.Tp)
                kf.append(seq.k_pages[layer, col])
                vf.append(seq.v_pages[layer, col])
                slots.append(slot)
            kv.pool.write_rows(kv.dtype, kf, slots, k_rows)
            kv.pool.write_rows(kv.dtype, vf, slots, v_rows)

    # -- scheduler ------------------------------------------------------------------

    def step(self, key=None) -> List[int]:
        """One engine iteration: admit one waiting request (prefill), then
        decode all active. Returns finished request ids."""
        key = key if key is not None else jax.random.PRNGKey(0)
        with tracing.span("engine.step"):
            if self.waiting:
                req = self.requests[self.waiting.pop(0)]
                tracing.wait("engine.queue", req.submitted, req.req_id)
                self._prefill(req)
                self.active.append(req.req_id)
            if self.active:
                self._decode_batch(self.active, key)
            finished = [r for r in self.active if self.requests[r].done]
            for r in finished:
                self.active.remove(r)
                self.kv.free_seq(self.requests[r].seq_id)
        return finished

    def run_to_completion(self, key=None, max_steps: int = 1000):
        if key is None:
            key = jax.random.PRNGKey(0)
        steps = 0
        while (self.waiting or self.active) and steps < max_steps:
            self.step(jax.random.fold_in(key, steps))
            steps += 1
        return {r.req_id: r.out_tokens for r in self.requests.values()}
