"""The traffic generator: a seed reproduces its schedule, every seed holds
the same arrival trace, and the seed draws the tokens."""
import numpy as np
import pytest

from chipbench import spec
from chipbench.traffic import generate

BIG = 2**31 + 12345


@pytest.mark.parametrize("mix", ["fork_invoke_short", "code_completion"])
def test_seed_reproduces(mix):
    m = spec.load_traffic(mix)
    a = generate.schedule(m, BIG, 30, 1000)
    b = generate.schedule(m, BIG, 30, 1000)
    assert [(r.due, r.max_tokens) for r in a] == [(r.due, r.max_tokens)
                                                  for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    c = generate.schedule(m, BIG + 2**32, 30, 1000)
    assert [r.due for r in a] == [r.due for r in c]
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


@pytest.mark.parametrize("mix", ["fork_invoke_short", "code_completion"])
def test_same_work_every_seed(mix):
    m = spec.load_traffic(mix)
    runs = [generate.schedule(m, s, 30, 1000) for s in (1, 2, BIG)]
    m2 = {**m, "arrivals": {**m["arrivals"], "order_seed": 7}}
    runs.append(generate.schedule(m2, 1, 30, 1000))
    assert [r.due for r in runs[3]] != [r.due for r in runs[0]]
    dues = [[r.due for r in s] for s in runs[:3]]
    lens = [sorted(len(r.prompt) for r in s) for s in runs]
    assert len({len(s) for s in runs}) == 1
    assert all(d == dues[0] for d in dues)
    assert all(x == lens[0] for x in lens)
    assert dues[0][0] == 0.0 and max(dues[0]) < 30
    assert len(runs[0]) == int(m["arrivals"]["rate_per_s"] * 30)
    assert {len(r.prompt) for r in runs[0]} <= set(m["prompt_tokens"]["values"])


def test_proportions_and_rate_override():
    assert list(generate.proportional_counts([0.5, 0.3, 0.2], 10)) == [5, 3, 2]
    assert sum(generate.proportional_counts([1, 1, 1], 20)) == 20
    m = spec.load_traffic("code_completion")
    assert len(generate.schedule(m, 3, 10, 100, rate=2.0)) == 20
    assert len(generate.schedule(m, 3, 0.1, 100)) == 1
    assert all(len(p) in m["prompt_tokens"]["values"]
               for p in generate.warm_prompts(m, 3, 100).values())
