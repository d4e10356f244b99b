"""The control, at a size a test run can hold: the reference computed in
float8 (the step below the configurations' bf16), put in the program's
place, has to come out as not correct, where the program itself, on the
same sample of the same run, is within the limit.

At this size the limit is set by the same rule as on the chip, from
these readings (CPU, seeds 1-3): the program's widest logit gap
0.0-0.0051, the control's 0.242-0.490.  The cells' own limits, set
from chip readings at their full size, are in ``bench/limits/``.
"""
import io

import pytest

from bench.harness import run_cell
from bench.tests import smoke

SMOKE_LIMIT = 0.06


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_fails_program_passes(seed):
    cell = "deepseek-7b.long-decode-sat"
    out = run_cell(cell, seed, 1.0, False, require_tpu=False,
                   compile_cache=False, model=smoke.smoke_model(cell),
                   mix_spec=smoke.smoke_mix(cell), control="fp8",
                   limit=SMOKE_LIMIT, log=io.StringIO())
    assert out["correct"] is False
    assert out["checks"]["logit_gap"]["limit"] == SMOKE_LIMIT
    assert out["control"]["program_logit_gap"] <= SMOKE_LIMIT
