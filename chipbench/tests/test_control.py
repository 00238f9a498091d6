"""The control: the reference computed in fp8, the step below the bf16 the
configuration computes in, put in the program's place, must come out not
correct through the harness's own verdict where the program comes out
correct."""
from chipbench.tests import tiny
from chipbench.tests.cells import run_tiny


def test_fp8_control_fails_where_the_program_passes():
    r = run_tiny("serve", control=True)
    limit = tiny.LIMITS["served_logit_gap"]
    assert r["readings"]["program_logit_gap"] <= limit
    assert not r["correct"]
    assert r["checks"]["served_logit_gap"]["value"] > limit
    assert r["checks"]["served_logit_gap"]["limit"] == limit

