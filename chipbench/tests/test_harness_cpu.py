"""run.py on the CPU: no chip, no result; the rehearsal prints one result
line per cell, correct, with no device metric."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    CELLS = [w["name"] for w in json.load(f)["workloads"]]
REQUIRED = ["correct", "attempted", "failed", "metrics", "device"]


def _run(*args, timeout=600):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, "chipbench/run.py", *args], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=timeout)


def test_without_a_chip_the_run_fails_and_prints_no_result():
    p = _run("--workload", CELLS[0], "--seed", "3000000001",
             "--seconds", "1", "--trace", "0", timeout=300)
    assert p.returncode != 0
    assert "no accelerator" in p.stderr
    assert not [line for line in p.stdout.splitlines()
                if line.startswith("{")]


@pytest.mark.parametrize("workload", CELLS)
def test_rehearsal_prints_one_correct_result_line(workload):
    p = _run("--workload", workload, "--seed", "2147483659",
             "--seconds", "0.5", "--trace", "0", "--rehearsal")
    assert p.returncode == 0, p.stderr[-4000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    keys = list(result)
    assert keys[:5] == REQUIRED and keys[-1] == "check"
    assert set(keys) <= set(REQUIRED) | {"breakdown", "check"}
    assert result["correct"] is True, result["check"]
    assert result["metrics"] == {}  # no device metric from the CPU
    assert result["device"]["platform"] == "cpu"
    assert result["attempted"] > 0 and result["failed"] == 0
    for row in result["check"].values():
        assert row["value"] <= row["limit"]
