"""The program's profiler names: every matmul and custom call of the train
step under one layer scope, and the train loop's host spans in a profile.

The scopes are what a device trace is split by (forward, backward and
recomputation of the blocks, the head and loss, the optimizer's update,
the projector refresh and each stage of its chain), so a matmul that lost
its scope is device time no reading can name.
"""
import glob
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from repro.configs.base import TrainConfig
from repro.configs.registry import get_config
from repro.core import make_optimizer
from repro.data.synthetic import SyntheticDataConfig, SyntheticDataset
from repro.models import build_model
from repro.train.loop import train_loop
from repro.train.state import TrainState
from repro.train.step import make_train_step

# one of these holds every matmul; the refresh chain's stages sit beneath
# opt_refresh, inside the optimizer's call
TOP = ("embed", "blocks", "head_loss", "opt_update")
CHAIN = ("sketch", "power_iter", "qr", "small_svd", "sara_sample")
OPS = re.compile(r"\s(dot|custom-call|convolution)\(")
OP_NAME = re.compile(r'op_name="([^"]*)"')
WRAPPED = re.compile(r"(?:jvp|transpose|vmap)\((.*)\)")


def _scopes(op_name):
    out = []
    for part in op_name.split("/"):
        while WRAPPED.fullmatch(part):
            part = WRAPPED.fullmatch(part).group(1)
        out.append(part)
    return out


@pytest.fixture(scope="module")
def job():
    # the benchmark's rehearsal size of Qwen2-1.5B
    cfg = get_config("qwen2-1.5b").with_(
        n_layers=2, d_model=128, n_heads=4, n_kv_heads=2, head_dim=32,
        d_ff=256, vocab_size=1024, loss_chunk=32, attn_chunk_q=16,
        attn_chunk_kv=16,
    )
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = make_optimizer(
        "galore-sara-adam", params, engine="bucketed",
        svd_backend="randomized", rank=32, sara_pool_factor=2,
        svd_power_iters=2, tau=2, grad_clip_norm=1.0,
    )
    state = TrainState(params, opt.init(params))
    batch = {"tokens": jnp.zeros((1, 128), jnp.int32),
             "labels": jnp.zeros((1, 128), jnp.int32)}
    return model, opt, state, batch, cfg


@pytest.mark.parametrize("refresh", [False, True])
def test_every_matmul_under_one_layer_scope(job, refresh):
    model, opt, state, batch, _ = job
    fns = make_train_step(model, opt, donate=False)
    if refresh:
        lowered = fns["jit_refresh_step"].lower(state, batch, group=0)
    else:
        lowered = fns["jit_step"].lower(state, batch)
    # XLA's CPU passes rebuild a few batched matmuls without a name; every
    # one that kept its name must keep its scope
    text = lowered.compile().as_text()
    names = [m.group(1) for line in text.splitlines()
             if OPS.search(line) and (m := OP_NAME.search(line))]
    assert names
    seen = set()
    for name in names:
        parts = _scopes(name)
        top = [p for p in parts if p in TOP]
        assert len(top) == 1, name
        chain = [p for p in parts if p in CHAIN]
        if chain:
            assert "opt_refresh" in parts[:parts.index(chain[0])], name
        seen.update(top + chain + [p for p in parts if p == "opt_refresh"])
    want = {"blocks", "head_loss", "opt_update"}
    if refresh:
        want |= {"opt_refresh", "sketch", "power_iter", "qr", "small_svd",
                 "sara_sample"}
    assert want <= seen, want - seen


def _host_events(root):
    (path,) = glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == "train" or ev.name.startswith("repro."):
                        out.append((ev.name, ev.start_ns, ev.start_ns
                                    + ev.duration_ns, dict(ev.stats)))
    return out


def test_train_loop_spans_in_a_cpu_profile(job, tmp_path):
    model, opt, state, _, cfg = job
    fns = make_train_step(model, opt, donate=False)
    data = SyntheticDataset(SyntheticDataConfig(
        vocab_size=cfg.vocab_size, seq_len=128, global_batch=1))
    tc = TrainConfig(total_steps=3, checkpoint_every=0,
                     checkpoint_dir=str(tmp_path / "ckpt"))
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        res = train_loop(model, opt, data, tc, fns, state=state,
                         log_every=1, handle_signals=False)
    finally:
        jax.profiler.stop_trace()
    assert res.final_step == 3
    events = _host_events(str(tmp_path / "trace"))
    steps = [e for e in events if e[0] == "train"]
    assert sorted(e[3]["step_num"] for e in steps) == [0, 1, 2]
    for span in ("repro.loop.data", "repro.loop.dispatch"):
        found = [e for e in events if e[0] == span]
        assert len(found) == 3, span
        for _, a, b, _ in found:  # each inside its step
            assert any(s <= a and b <= t for _, s, t, _ in steps), span
    assert [e for e in events if e[0] == "repro.loop.fetch"]
