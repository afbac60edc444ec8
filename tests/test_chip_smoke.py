"""chip_smoke.py on the CPU: the tiny rehearsal runs its train and serve
phases end to end, and without ``--cpu-rehearsal`` the script refuses to
run on a backend that is not a TPU."""
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, tmp_path, timeout):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO,
    )


def test_cpu_rehearsal_runs_train_and_serve(tmp_path):
    out = _run(["--cpu-rehearsal"], tmp_path, timeout=600)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    lines = out.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 1}}
    text = out.stdout
    assert "[train] losses" in text and "checkpoint written" in text
    assert "params restored from checkpoint step 8" in text
    assert "[serve] 4 requests" in text


def test_refuses_without_tpu(tmp_path):
    out = _run([], tmp_path, timeout=300)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert '"ok"' not in out.stdout  # no result line
