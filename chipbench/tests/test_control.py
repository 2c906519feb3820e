"""The control: the reference put in the program's place one precision
below the configuration's (float8 e4m3 for bfloat16) comes out not
correct through the same verdict, on three seeds, where the program on
the same sample is correct.  At this test's size the
logits are small, so the limit is this size's own, set as the cells'
limits are: above every program reading, below every control reading."""
import time

import pytest

from chipbench import check, run
from chipbench.tests.helpers import tiny_conf, tiny_spec

# readings at this size (CPU, bfloat16 program, 3 seeds each config):
# program 0.0 to 0.0036, control (fp8) 0.076 to 0.10; int8, read beside
# it, 0.007 to 0.03 is too close to the program here to be the control
TINY_LIMIT = 0.01


@pytest.mark.parametrize("name", ["internlm2-1.8b", "mha-layernorm"])
def test_control_is_not_correct(name):
    conf = tiny_conf(name, limit=TINY_LIMIT)
    spec = dict(tiny_spec(), check_requests=8,
                output={"dist": "lognormal", "median": 24, "sigma": 0.5,
                        "min": 8, "max": 40})
    for seed in (1, 2, 2 ** 31 + 3):
        served = run.serve_cell(conf, spec, seed=seed, seconds=3.0,
                                trace=False, t_start=time.perf_counter(),
                                clock=run.CompileClock())
        v = check.check_served(conf, spec, seed, served, controls=("fp8",))
        nums = {k: x["value"] for k, x in v["numbers"].items()}
        assert v["correct"], nums
        assert nums["checked_tokens_at_least"] >= 20, nums
        ctl = v["controls"]["fp8"]
        assert not ctl["correct"], ctl["numbers"]
