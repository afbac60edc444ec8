"""The correctness check fails what it has to: the program with its timed
path broken underneath (a run's own path, at the rehearsal size on the
CPU, with no look for a chip), and the control, the reference computed in
float8 in the program's place."""
import json
import os

import pytest

from chipbench import calibrate, check, run
from chipbench import cell as cell_lib

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    CELLS = [w["name"] for w in json.load(f)["workloads"]]


@pytest.mark.parametrize("fault", ["unchanged", "half"])
@pytest.mark.parametrize("workload", CELLS)
def test_a_broken_step_is_not_correct(workload, fault):
    result = run.run_cell(workload, 3000000017, 0.3, False, rehearsal=True,
                          fault=fault)
    assert result["correct"] is False, result["check"]


@pytest.mark.parametrize("workload", CELLS)
def test_the_float8_control_is_not_correct(workload):
    limits = cell_lib.load_cell(workload, rehearsal=True).traffic[
        "rehearsal_limits"]
    (row,) = calibrate.readings(workload, [3000000019], ["fp8"],
                                rehearsal=True, program=False)
    ok, table = check.verdict(row, limits)
    assert ok is False, table
