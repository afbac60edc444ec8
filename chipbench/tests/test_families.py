"""Model families: the dense decoder's layout, the loader's refusal of a
family it does not know, and a new family brought to the harness with new
files and new BENCHMARK.json entries alone."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import families, weights

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "chipbench")

# the leaves of qwen2-1.5b at 13 layers, in the weights' order
QWEN2_1_5B_LEAVES = [
    ("['blocks']['attn_norm']", (13, 1536)),
    ("['blocks']['k_bias']", (13, 256)),
    ("['blocks']['k_proj']", (13, 1536, 256)),
    ("['blocks']['mlp']['down_proj']", (13, 8960, 1536)),
    ("['blocks']['mlp']['gate_proj']", (13, 1536, 8960)),
    ("['blocks']['mlp']['up_proj']", (13, 1536, 8960)),
    ("['blocks']['mlp_norm']", (13, 1536)),
    ("['blocks']['o_proj']", (13, 1536, 1536)),
    ("['blocks']['q_bias']", (13, 1536)),
    ("['blocks']['q_proj']", (13, 1536, 1536)),
    ("['blocks']['v_bias']", (13, 256)),
    ("['blocks']['v_proj']", (13, 1536, 256)),
    ("['embed']", (151936, 1536)),
    ("['final_norm']", (1536,)),
]


def _qwen2_config():
    with open(os.path.join(BENCH, "configs", "qwen2-1.5b.json")) as f:
        return json.load(f)


def test_dense_leaves_of_qwen2_1_5b():
    config = _qwen2_config()
    assert families.load(config).__name__ == "chipbench.families.dense"
    layout = weights.leaves(config)
    assert [(x.path, x.shape) for x in layout] == QWEN2_1_5B_LEAVES
    for x in layout:
        assert x.stack == (1 if x.path.startswith("['blocks']") else 0)
        assert x.lowrank == ("_proj" in x.path)
        assert x.ones == x.path.endswith("norm']")


@pytest.mark.parametrize("family", [None, "granite"])
def test_an_unknown_family_is_refused(family):
    config = {k: v for k, v in _qwen2_config().items() if k != "family"}
    if family is not None:
        config["family"] = family
    with pytest.raises(SystemExit, match=r"found \[.*'dense'.*\]"):
        families.load(config)


ECHO = '''"""The dense decoder under another name; it writes the name of each of
its functions the harness calls to the file FAMILY_CALLS names."""
import os

from chipbench.families import dense
from chipbench.families.dense import *  # noqa: F401,F403


def _called(name):
    with open(os.environ["FAMILY_CALLS"], "a") as f:
        f.write(name + "\\n")


def shapes(config):
    _called("shapes")
    return dense.shapes(config)


def loss(*args, **kwargs):
    _called("loss")
    return dense.loss(*args, **kwargs)
'''


def _only_added(old, new) -> bool:
    """``new`` is ``old`` with items appended to its lists, and no other
    difference."""
    if isinstance(old, list):
        return (isinstance(new, list) and len(new) >= len(old)
                and all(_only_added(a, b) for a, b in zip(old, new)))
    if isinstance(old, dict):
        return (isinstance(new, dict) and set(old) == set(new)
                and all(_only_added(old[k], new[k]) for k in old))
    return old == new


def _digests(top):
    out = {}
    for d, dirs, files in os.walk(top):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        for name in files:
            path = os.path.join(d, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, top)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


def test_a_new_family_runs_through_the_rehearsal(tmp_path):
    """A copy of the checkout gains a family module, a configuration, a
    workload and BENCHMARK.json entries, and no harness file changes; its
    cell's rehearsal gives one correct result line through the family."""
    tree = tmp_path / "checkout"
    shutil.copytree(BENCH, tree / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "src"), tree / "src")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        before = f.read()
    bench = json.loads(before)

    (tree / "chipbench" / "families" / "dense_echo.py").write_text(ECHO)
    config = {**_qwen2_config(), "name": "echo", "family": "dense_echo"}
    (tree / "chipbench" / "configs" / "echo.json").write_text(
        json.dumps(config))
    with open(os.path.join(BENCH, "workloads", "qwen2-1.5b.train.json")) as f:
        workload = json.load(f)
    workload.update(name="echo.train", config="echo")
    (tree / "chipbench" / "workloads" / "echo.train.json").write_text(
        json.dumps(workload))
    bench["configs"].append({
        "name": "echo", "source": config["source"],
        "file": "chipbench/configs/echo.json",
        "reduced": config["reduced"], "why": "the dense decoder renamed"})
    bench["workloads"].append({
        "name": "echo.train", "config": "echo", "traffic": "train",
        "chips": 1, "why": workload["why"]})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "qwen2-1.5b.train" in metric.get("workloads", []):
            metric["workloads"].append("echo.train")
    (tree / "BENCHMARK.json").write_text(json.dumps(bench))

    # every harness file as it is in the checkout; the BENCHMARK.json
    # entries only added to
    theirs = _digests(tree / "chipbench")
    for path, digest in _digests(BENCH).items():
        assert theirs.pop(path) == digest, path
    assert sorted(theirs) == ["configs/echo.json", "families/dense_echo.py",
                              "workloads/echo.train.json"]
    assert _only_added(json.loads(before), bench)

    calls = tmp_path / "calls.txt"
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "FAMILY_CALLS": str(calls)}
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "echo.train",
         "--seed", "2147483659", "--seconds", "0.5", "--trace", "0",
         "--rehearsal"],
        cwd=tree, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["correct"] is True, result["check"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(calls.read_text().split()) == {"shapes", "loss"}
