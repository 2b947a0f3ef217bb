"""Every cell end to end at a tiny size on the CPU: set-up, traffic and
the comparison with the reference, which the program passes and the
control (the reference in bfloat16 in the program's place) fails."""
import json
import os

import pytest

from bench import oracle
from bench.cpu_scale import run_tiny

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    CELLS = [c["name"] for c in json.load(_f)["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_correct_and_control_fails(cell):
    result, numbers, limits = run_tiny(cell, seed=2 ** 31 + 11,
                                       control=True)
    assert result["correct"], numbers
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "checks"
    assert set(result["checks"]) == set(limits)
    assert "setup_s" in result["metrics"]
    control = numbers["control"]
    assert not oracle.judge(control, limits), control
    # the numbers the control fails on every seed, it fails by far
    for k in ("rows_bad_pct", "standing_gap"):
        assert control[k] > 10 * limits[k], (k, control[k])
    assert control["centers_gap"] > limits["centers_gap"]
