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



def test_fp8_control_fails_the_fork_cell_where_the_program_passes():
    """The same on the fork cell's path, with the eight tokens an
    invocation serves."""
    r = run_tiny("fork", control=True, output_tokens=8)
    limit = tiny.LIMITS["served_logit_gap"]
    assert r["readings"]["program_logit_gap"] <= limit
    assert r["readings"]["child_params_differ"] == 0
    assert not r["correct"]
    assert r["checks"]["served_logit_gap"]["value"] > limit
