"""What decides ``correct``: the served tokens against the plain reference,
and, for forked children, their parameters against the seed's, bit for bit.

* ``served_logit_gap``: over a sample of finished requests drawn from the
  seed (the longest always in it), the widest gap by which a served token's
  reference logit lies below the reference's best at its position.  Greedy
  decoding serves the program's best token, so a sound program's gap is its
  rounding; a wrong page, mask, position or token shows as a wide gap.
* ``child_params_differ``: invocations whose materialized child parameters
  differ from the seed's weights, by per-leaf bit sums (limit 0).

The reference runs after the window closed and the program's state is
freed, on weights it makes again from the seed.
"""
from __future__ import annotations

import numpy as np

from chipbench import model, reference
from chipbench.system import leaf_sums
from chipbench.traffic.generate import rng


def sample(recs: list, n: int, seed: int) -> list:
    """``n`` finished requests drawn from the seed, the longest among them."""
    done = [r for r in recs if not r["failed"]]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r["prompt"]) + len(r["tokens"]),
                                       -r["idx"]))
    rest = [r for r in done if r is not longest]
    pick = rng(seed, 3).permutation(len(rest))[:max(0, n - 1)]
    return [longest] + [rest[i] for i in sorted(pick)]


def readings(cfg, dm, seed: int, picked: list, sums: dict | None = None,
             control: bool = False) -> dict:
    """The numbers compared, for ``picked`` requests (and ``sums``, the
    children's per-leaf bit sums by request idx).  ``control`` puts the
    fp8 control in the program's place: ``served_logit_gap`` is then the
    gap of the tokens it puts first at the same positions, and the
    program's own gap is kept as ``program_logit_gap``."""
    w = model.make_weights_fn(cfg, dm)(model.seed_key(seed))

    def widest(fp8):
        gaps = [reference.served_gaps(w, dm, r["prompt"], r["tokens"], fp8=fp8)
                for r in picked]
        return (float(max((g.max() for g in gaps), default=np.inf)),
                int(sum(g.size for g in gaps)))

    gap, n = widest(False)
    out = {"served_logit_gap": gap, "served_tokens_compared": n}
    if control:
        out["program_logit_gap"] = gap
        out["served_logit_gap"] = widest(True)[0]
    if sums is not None:
        want = np.asarray(leaf_sums(w))
        out["child_params_differ"] = int(sum(
            not np.array_equal(np.asarray(s), want) for s in sums.values()))
    return out


def verdict(read: dict, limits: dict, failed: int) -> tuple:
    """(correct, {name: {"value", "limit"}}) for the numbers ``limits``
    names."""
    checks = {k: {"value": read[k], "limit": limits[k]} for k in limits}
    ok = failed == 0 and all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
                             for c in checks.values())
    return ok, checks
