"""The control comes out incorrect: the program's own lower-precision
serving path (Tensorizer W8A8) in place of the bf16 weights the
configuration states, at a size a CPU test holds, judged on the number
each family's configuration file compares. At these sizes, over seeds 1-6
(dense) and 1-9 (moe), the bf16 program read mean_logit_gap
0.00005-0.00029 and untied_mean_gap 0.00023-0.00361, the control
0.00099-0.00212 and 0.00812-0.02489; the limits here lie between."""
import time

import pytest

import _bench_path  # noqa: F401
from smoke_sizes import MID_CELL, MID_MIX, mid_config
from bench import harness

MID_LIMITS = {"dense": {"mean_logit_gap": 0.0006},
              "moe": {"untied_mean_gap": 0.0055}}


@pytest.mark.parametrize("family", ["dense", "moe"])
@pytest.mark.parametrize("quantize,correct", [("off", True),
                                              ("serve", False)])
def test_control_fails_where_the_program_passes(family, quantize, correct):
    res = harness.run_cell(
        mid_config(family, MID_LIMITS[family]), MID_MIX, MID_CELL, 5, 3.0,
        False, [], t_process=time.monotonic(), quantize=quantize,
        log=lambda s: None)
    assert res["correct"] is correct, res["checks"]
    assert set(res["checks"]) == {"lost", "short"} | set(MID_LIMITS[family])
