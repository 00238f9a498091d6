"""The one traffic generator: a mix file's parameters and a seed make the
schedule of requests.

The ``n = rate x seconds`` (rounded down) arrivals are spaced by the
exponential distribution's quantiles at ``(i + 0.5) / n``, scaled to sum to
``n / rate`` (a Poisson process's gaps, without the draw-to-draw spread of
their sum); the first is due at 0.  The prompt lengths come
in the mix's exact proportions.  The mix's ``order_seed`` orders both, so a
mix is one fixed arrival trace; ``--seed`` draws the prompt tokens (and the
harness the weights).  With tens of requests in a window, reordering the
gaps alone moves a 95th percentile by 20-40 %, which would hide any change
a later PR makes; a fixed trace leaves only the system's own variation.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Request:
    idx: int
    due: float               # seconds after the window opens
    prompt: np.ndarray       # int32 token ids
    max_tokens: int


def rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2**64 - 1), *stream])


def proportional_counts(weights, n: int) -> np.ndarray:
    """``n`` split by ``weights`` by largest remainder."""
    w = np.asarray(weights, float)
    exact = w / w.sum() * n
    counts = np.floor(exact).astype(int)
    order = np.argsort(-(exact - counts), kind="stable")
    counts[order[:n - counts.sum()]] += 1
    return counts


def schedule(mix: dict, seed: int, seconds: float, vocab: int,
             rate: float | None = None) -> list:
    """Requests due in a window of ``seconds``; ``rate`` overrides the mix's
    arrival rate (the knee sweep)."""
    arr = mix["arrivals"]
    if arr["process"] != "poisson":
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    rate = float(rate if rate is not None else arr["rate_per_s"])
    n = max(1, int(rate * seconds))
    g = rng(int(arr["order_seed"]), 0)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps = g.permutation(gaps) * (n / rate / gaps.sum())
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])    # the last < seconds
    p = mix["prompt_tokens"]
    lens = np.repeat(p["values"], proportional_counts(p["weights"], n))
    lens = g.permutation(lens)
    toks = rng(seed, 1)
    return [Request(i, float(due[i]),
                    toks.integers(0, vocab, int(lens[i])).astype(np.int32),
                    int(mix["output_tokens"]))
            for i in range(n)]


def warm_prompts(mix: dict, seed: int, vocab: int) -> dict:
    """One prompt of each length the mix can draw, for warming up."""
    g = rng(seed, 2)
    return {int(n): g.integers(0, vocab, int(n)).astype(np.int32)
            for n in mix["prompt_tokens"]["values"]}
