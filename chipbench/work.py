"""Operations and bytes that each kernel call and each model step require,
computed from shapes alone.

The counts are what the work needs, whatever implements it: no padding, no
whole-pool copies, no recomputation.  A roofline share is the least time
the chip could take, ``max(flops / peak FLOP/s, bytes / peak bytes/s)``,
over the measured time; with required work in the numerator it cannot pass
100% unless the time leaves out part of the work.
"""
from __future__ import annotations

import json
from pathlib import Path

BF16 = 2


def peaks(device_kind: str) -> dict:
    table = json.loads((Path(__file__).parent / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table['devices'])}")
    return table["devices"][device_kind]


def least_seconds(flops: float, nbytes: float, pk: dict) -> float:
    return max(flops / pk["bf16_flops_per_s"], nbytes / pk["hbm_bytes_per_s"])


# -- page kernels -------------------------------------------------------------


def page_move_bytes(pages: int, page_bytes: int) -> int:
    """page_gather or cow_scatter over ``pages`` pages: each page read once
    and written once.  gather_assemble needs no more: its pages are read
    once and land once in the tensor's layout; the trim and reshape after
    the gather are copies the work does not require."""
    return 2 * pages * page_bytes


# -- model steps --------------------------------------------------------------


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
