"""Fault-matrix tests for the degrade-and-recover runtime (DESIGN.md §2.9).

Every injected fault class -- non-finite grads, non-finite loss streak,
corrupt checkpoint, save failure, preemption -- must complete training
without an abort under the default RecoveryPolicy; with no fault injected
the recovery-enabled loop must be bit-identical to the plain one.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import TrainConfig
from repro.configs.registry import get_config
from repro.core import make_optimizer
from repro.core import metrics as metrics_lib
from repro.core.projectors import refresh_is_stochastic
from repro.data.synthetic import SyntheticDataConfig, SyntheticDataset
from repro.models import build_model
from repro.train import checkpoint as ckpt_lib
from repro.train import recovery as recovery_lib
from repro.train.faults import FaultPlan, FaultSpec
from repro.train.loop import train_loop
from repro.train.monitor import HeartbeatRegistry
from repro.train.recovery import RecoveryPolicy
from repro.train.step import make_train_step

POLICY = RecoveryPolicy()  # defaults: skip + rollback, no backoff sleep


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("llama3-8b", smoke=True).with_(dtype=jnp.float32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = make_optimizer("galore-sara-adam", params, rank=8, tau=4, lr=2e-3)
    data = SyntheticDataset(
        SyntheticDataConfig(
            vocab_size=cfg.vocab_size, seq_len=32, global_batch=4
        )
    )
    fns_rec = make_train_step(model, opt, donate=False, recovery=POLICY)
    fns_plain = make_train_step(model, opt, donate=False)
    return model, opt, data, fns_rec, fns_plain


def _tc(tmp_path, name, **kw):
    kw.setdefault("total_steps", 14)
    kw.setdefault("checkpoint_every", 0)
    kw.setdefault("async_checkpoint", False)
    return TrainConfig(
        lr=2e-3, checkpoint_dir=str(tmp_path / name), **kw
    )


def _run(setup, tc, *, recovery=POLICY, plan=None, plain=False, **kw):
    model, opt, data, fns_rec, fns_plain = setup
    return train_loop(
        model, opt, data, tc,
        fns_plain if plain else fns_rec,
        log_every=1, handle_signals=False,
        recovery=None if plain else recovery, fault_plan=plan, **kw
    )


def _last_rec(res):
    return [r for r in res.history if "skip_steps" in r][-1]


# ---------------------------------------------------------------------------
# no fault injected -> zero recovery events, bit-identical losses
# ---------------------------------------------------------------------------


def test_no_fault_is_bit_identical_and_quiet(setup, tmp_path):
    # an armed-but-empty FaultPlan must be invisible: bit-identical to the
    # same recovery-enabled program running with no plan at all
    res_none = _run(setup, _tc(tmp_path, "none"), plan=None)
    plan = FaultPlan()
    res_rec = _run(setup, _tc(tmp_path, "rec"), plan=plan)
    np.testing.assert_array_equal(
        np.asarray(res_none.losses), np.asarray(res_rec.losses)
    )
    # vs. the recovery-free program: the gate selects the new values
    # exactly, but compiling the finite-check in changes XLA fusion, so
    # cross-program equality is only up to rounding (same tolerance the
    # resume tests use)
    res_plain = _run(setup, _tc(tmp_path, "plain"), plain=True)
    np.testing.assert_allclose(
        np.asarray(res_plain.losses), np.asarray(res_rec.losses), atol=1e-6
    )
    assert plan.fired == []
    assert not [r for r in res_rec.history if "event" in r]
    last = _last_rec(res_rec)
    assert last["skip_steps"] == 0.0
    assert last["rollbacks"] == 0.0
    assert last["save_failures"] == 0.0


# ---------------------------------------------------------------------------
# non-finite grads -> skip-step (params and moments untouched)
# ---------------------------------------------------------------------------


def test_nonfinite_grads_skip_the_update(setup, tmp_path):
    plan = FaultPlan([
        FaultSpec("nan_grads", step=5),
        FaultSpec("inf_grads", step=9),
    ])
    res = _run(setup, _tc(tmp_path, "skip"), plan=plan)
    assert res.final_step == 14
    assert plan.fired == [("nan_grads", 5), ("inf_grads", 9)]
    # forward pass is unaffected -- only the update was gated out
    assert np.isfinite(res.losses).all()
    last = _last_rec(res)
    assert last["skip_steps"] == 2.0
    assert last["rollbacks"] == 0.0  # isolated bad steps never escalate
    # the optimizer step counter only advances on applied updates
    assert int(jax.device_get(res.state.opt_state.step)) == 12


def test_skipped_step_leaves_prefix_bit_identical(setup, tmp_path):
    """A skipped step must be a true no-op on everything before it: the
    faulted run matches the fault-free run bit-for-bit through the loss of
    the skipped step itself (the loss is computed before the update), and
    only diverges afterwards because the clean run applied one more
    update."""
    res_clean = _run(setup, _tc(tmp_path, "clean"), plan=FaultPlan())
    plan = FaultPlan([FaultSpec("nan_grads", step=5)])
    res = _run(setup, _tc(tmp_path, "skip2"), plan=plan)
    np.testing.assert_array_equal(
        np.asarray(res.losses[:6]), np.asarray(res_clean.losses[:6])
    )
    # from step 6 on the trajectories differ by exactly one applied update
    assert any(
        a != b for a, b in zip(res.losses[6:], res_clean.losses[6:])
    )


# ---------------------------------------------------------------------------
# sustained non-finite loss -> rollback to last checkpoint and resample
# ---------------------------------------------------------------------------


def test_nan_loss_streak_rolls_back(setup, tmp_path):
    plan = FaultPlan([
        FaultSpec("nan_loss", step=s) for s in (9, 10, 11)
    ])
    tc = _tc(tmp_path, "roll", checkpoint_every=4)
    res = _run(setup, tc, plan=plan)
    assert res.final_step == 14
    events = [r for r in res.history if r.get("event") == "rollback"]
    assert len(events) == 1
    # checkpoints at 0 (initial pin), 4, 8; streak trips at step 11
    assert events[0]["step"] == 8.0
    assert events[0]["from_step"] == 11.0
    assert events[0]["attempt"] == 1.0
    # the NaN entries belong to the abandoned trajectory: truncated
    assert len(res.losses) == 14
    assert np.isfinite(res.losses).all()
    assert _last_rec(res)["rollbacks"] == 1.0


def test_rollback_resample_changes_trajectory(setup, tmp_path):
    """After the rollback the refresh RNG is re-seeded: the replayed steps
    draw a different SARA subspace and the losses diverge from the clean
    run -- the run does not deterministically replay into the same fault."""
    res_clean = _run(setup, _tc(tmp_path, "rclean"), plan=FaultPlan())
    plan = FaultPlan([
        FaultSpec("nan_loss", step=s) for s in (9, 10, 11)
    ])
    tc = _tc(tmp_path, "rfault", checkpoint_every=4)
    res = _run(setup, tc, plan=plan)
    # pre-divergence prefix is untouched
    np.testing.assert_array_equal(
        np.asarray(res.losses[:8]), np.asarray(res_clean.losses[:8])
    )
    # replayed step 8 is a refresh step (tau=4) under the folded key:
    # some post-rollback loss must differ from the clean trajectory
    assert any(
        a != b for a, b in zip(res.losses[8:], res_clean.losses[8:])
    )


def test_rollback_budget_exhausted_aborts(setup, tmp_path):
    # faults re-fire once after the rollback (times=2): divergence
    # persists past max_rollbacks=1 -> classic sentinel abort
    policy = RecoveryPolicy(max_rollbacks=1)
    plan = FaultPlan([
        FaultSpec("nan_loss", step=s, times=2) for s in (2, 3, 4)
    ])
    with pytest.raises(FloatingPointError, match="rollback"):
        _run(setup, _tc(tmp_path, "budget"), recovery=policy, plan=plan)


# ---------------------------------------------------------------------------
# corrupt checkpoint -> rollback falls back to an older verified one
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "kind", ["ckpt_corrupt_leaf", "ckpt_truncate_manifest"]
)
def test_rollback_falls_back_past_corrupt_checkpoint(setup, tmp_path, kind):
    # save ordinal 2 is the step-8 checkpoint (0 = initial pin, 1 = step 4)
    plan = FaultPlan(
        [FaultSpec(kind, save_index=2)]
        + [FaultSpec("nan_loss", step=s) for s in (9, 10, 11)]
    )
    tc = _tc(tmp_path, f"fb_{kind}", checkpoint_every=4)
    res = _run(setup, tc, plan=plan)
    assert res.final_step == 14
    assert ("nan_loss", 11) in plan.fired and (kind, 2) in plan.fired
    events = [r for r in res.history if r.get("event") == "rollback"]
    # step-8 checkpoint fails verification -> rollback lands on step 4
    assert len(events) == 1 and events[0]["step"] == 4.0
    assert len(res.losses) == 14 and np.isfinite(res.losses).all()
    # the replay re-saved step 8 cleanly over the corrupt directory
    assert ckpt_lib.verify_checkpoint(tc.checkpoint_dir, 8)


def test_resume_from_corrupt_newest_checkpoint(setup, tmp_path):
    """Crash-restart flavor of fallback: the *initial* restore of a fresh
    loop walks past a corrupt newest checkpoint and the resumed trajectory
    is bit-identical to the uninterrupted run."""
    tc = _tc(tmp_path, "boot", total_steps=12, checkpoint_every=4)
    res1 = _run(setup, tc, plan=None)
    cdir = os.path.join(tc.checkpoint_dir, "step_00000012")
    victim = sorted(
        f for f in os.listdir(cdir) if f.endswith(".npy")
    )[0]
    with open(os.path.join(cdir, victim), "r+b") as f:
        f.seek(64)
        f.write(b"\xff" * 16)
    res2 = _run(setup, tc, plan=None)  # restores 12 -> corrupt -> 8
    np.testing.assert_array_equal(
        np.asarray(res1.losses[8:]), np.asarray(res2.losses)
    )


# ---------------------------------------------------------------------------
# checkpoint write failure -> retried; persistent failure -> counted
# ---------------------------------------------------------------------------


def test_save_write_error_is_retried(setup, tmp_path):
    plan = FaultPlan([FaultSpec("ckpt_write_error", save_index=1, times=1)])
    tc = _tc(tmp_path, "retry", total_steps=8, checkpoint_every=4)
    res = _run(setup, tc, plan=plan)
    assert res.final_step == 8
    last = _last_rec(res)
    assert last["save_retries"] >= 1.0
    assert last["save_failures"] == 0.0
    assert ckpt_lib.verify_checkpoint(tc.checkpoint_dir, 4)


def test_persistent_save_failure_does_not_abort(setup, tmp_path):
    # fails every attempt of save ordinal 1 (budget > retries)
    plan = FaultPlan([FaultSpec("ckpt_write_error", save_index=1, times=10)])
    tc = _tc(tmp_path, "sfail", total_steps=8, checkpoint_every=4)
    res = _run(setup, tc, plan=plan)
    assert res.final_step == 8  # training survived the lost checkpoint
    assert _last_rec(res)["save_failures"] >= 1.0
    assert [r for r in res.history if r.get("event") == "save_failed"]
    # the step-4 save was lost; step 8 (a later ordinal) landed fine
    assert not os.path.isdir(os.path.join(tc.checkpoint_dir, "step_00000004"))
    assert ckpt_lib.verify_checkpoint(tc.checkpoint_dir, 8)


def test_async_save_failure_surfaces_without_abort(setup, tmp_path):
    """Async flavor: the write fails on the background thread; the error
    surfaces at the next save's drain as a counted event, never an abort,
    and later saves still land."""
    plan = FaultPlan([FaultSpec("ckpt_write_error", save_index=1, times=10)])
    tc = _tc(
        tmp_path, "asfail", total_steps=8, checkpoint_every=4,
        async_checkpoint=True,
    )
    res = _run(setup, tc, plan=plan)
    assert res.final_step == 8
    assert [r for r in res.history if r.get("event") == "save_failed"]
    assert not os.path.isdir(os.path.join(tc.checkpoint_dir, "step_00000004"))
    assert ckpt_lib.verify_checkpoint(tc.checkpoint_dir, 8)


# ---------------------------------------------------------------------------
# preemption / straggler / heartbeat
# ---------------------------------------------------------------------------


def test_preemption_checkpoint_and_resume(setup, tmp_path):
    tc_clean = _tc(tmp_path, "pclean", total_steps=12, checkpoint_every=4)
    res_clean = _run(setup, tc_clean, plan=None)
    plan = FaultPlan([FaultSpec("preempt", step=6)])
    tc = _tc(tmp_path, "pre", total_steps=12, checkpoint_every=4)
    res1 = _run(setup, tc, plan=plan)
    assert res1.final_step == 7  # finished step 6, checkpointed, exited
    assert plan.fired == [("preempt", 6)]
    assert ckpt_lib.latest_step(tc.checkpoint_dir) == 7
    res2 = _run(setup, tc, plan=None)  # resume to completion
    np.testing.assert_array_equal(
        np.asarray(res1.losses + res2.losses),
        np.asarray(res_clean.losses),
    )


def test_slow_step_and_heartbeat(setup, tmp_path):
    plan = FaultPlan([FaultSpec("slow_step", step=3, value=0.2)])
    hb = HeartbeatRegistry(timeout_s=60.0)
    tc = _tc(tmp_path, "slow", total_steps=6)
    res = _run(setup, tc, plan=plan, heartbeats=hb, worker_name="w0")
    assert res.final_step == 6
    assert plan.fired == [("slow_step", 3)]
    # the loop beat every step; nobody is stale
    assert hb.stale() == []
    assert _last_rec(res)["stale_workers"] == 0.0
    # the injected sleep shows up in the step time of the fetch that ends
    # step 3 (log_every=1: one fetch per step), and in no other
    steps = {r["step"]: r for r in res.history
             if "step" in r and "event" not in r}
    assert steps[3.0]["step_time_s"] >= 0.2
    assert steps[2.0]["step_time_s"] < 0.2 and steps[5.0]["step_time_s"] < 0.2


# ---------------------------------------------------------------------------
# resample semantics: stochastic methods move, dominant cannot
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "name,method",
    [
        ("galore-sara-adam", "sara"),
        ("golore-adam", "golore"),
        ("galore-adam", "dominant"),
    ],
)
def test_resample_moves_stochastic_subspaces_only(name, method):
    params = {
        "w": jax.random.normal(jax.random.PRNGKey(1), (48, 96), jnp.float32)
    }
    grads = {
        "w": jax.random.normal(jax.random.PRNGKey(2), (48, 96), jnp.float32)
    }
    opt = make_optimizer(name, params, rank=8, tau=1, lr=1e-3)

    def refreshed_projector(state):
        _, new_state, _ = opt.update(grads, state, params, refresh=True)
        projs = metrics_lib.collect_projectors(
            new_state, opt.specs, layout=opt.state_layout
        )
        (p,) = projs.values()
        return np.asarray(p)

    st = opt.init(params)
    p_a = refreshed_projector(st)
    p_b = refreshed_projector(st)
    np.testing.assert_array_equal(p_a, p_b)  # replay is deterministic
    p_c = refreshed_projector(recovery_lib.resample_opt_state(st, 1))
    overlap = float(
        metrics_lib.subspace_overlap(jnp.asarray(p_a), jnp.asarray(p_c))
    )
    if refresh_is_stochastic(method):
        # a genuinely different subspace: strictly less than full overlap
        assert overlap < 0.999, (method, overlap)
    else:
        # dominant is a deterministic function of G: the key fold is a
        # no-op on the selected subspace (the frozen-subspace failure
        # mode the paper targets)
        assert method == "dominant"
        np.testing.assert_allclose(p_a, p_c, rtol=0, atol=0)
        assert overlap > 0.999999


def test_resample_distinct_attempts_distinct_keys():
    params = {
        "w": jax.random.normal(jax.random.PRNGKey(1), (32, 64), jnp.float32)
    }
    opt = make_optimizer("galore-sara-adam", params, rank=4, tau=1)
    st = opt.init(params)
    k1 = recovery_lib.resample_opt_state(st, 1).key
    k2 = recovery_lib.resample_opt_state(st, 2).key
    assert not np.array_equal(np.asarray(k1), np.asarray(k2))
    assert not np.array_equal(np.asarray(k1), np.asarray(st.key))


def test_zero_sharded_skip_gate_lockstep():
    """ISSUE 7: with state_sharding='zero' each shard's finite check sees
    only its LOCAL rows of the reduced gradient stacks, so the gate psums
    ONE scalar verdict across shards -- poisoning a single shard's rows
    must make EVERY shard skip (state bit-unchanged everywhere), else the
    sharded optimizer states diverge.  Runs in a subprocess on 8 fake
    devices (the dry-run rule: only dryrun.py forces device counts)."""
    import subprocess
    import sys
    import textwrap

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.core import make_optimizer
    from repro.core import buckets as buckets_lib
    from repro.core.lowrank import StackedGrads, project_grads_stacked
    from repro.launch.mesh import make_mesh
    from repro.launch import sharding as shd
    from repro.train.state import TrainState

    key = jax.random.PRNGKey(0)
    mat = lambda i, s: jax.random.normal(jax.random.fold_in(key, i), s) * 0.02
    params = {
        "q_proj": mat(0, (3, 32, 64)),
        "k_proj": mat(1, (3, 32, 64)),
        "o_single": mat(2, (32, 64)),
        "up_proj": mat(3, (3, 32, 96)),
        "down_proj": mat(4, (3, 96, 32)),
    }
    opt = make_optimizer("galore-sara-adam", params, rank=16, lr=1e-2,
                         alpha=0.5, min_dim=8, engine="bucketed",
                         state_sharding="zero", state_shards=4)
    st = opt.init(params)
    g0 = jax.tree_util.tree_map(lambda p: jnp.ones_like(p) * 0.01, params)
    _, st, _ = opt.update(g0, st, params, refresh=True, apply=True)

    # padded (B_pad, r, n) R-space stacks, as the reduce-scatter produces
    sg = project_grads_stacked(opt, g0, st)
    padded = list(buckets_lib.zero_pad_grad_stacks(opt.state_layout,
                                                   sg.buckets))
    assert sg.rest == ()
    rows = padded[0].shape[0] // 4  # rows owned by ONE shard
    bad0 = padded[0].at[:rows].set(jnp.nan)  # poison shard 0 only
    sg_bad = StackedGrads(buckets=(bad0,) + tuple(padded[1:]), rest=())
    sg_ok = StackedGrads(buckets=tuple(padded), rest=())

    mesh = make_mesh((4, 2))
    state = TrainState(params, st)
    sspec = shd.zero_state_specs(state, ("data",))
    gspec = StackedGrads(
        buckets=tuple(P("data") for _ in padded), rest=())

    def body(state, sg):
        p2, st2, aux = opt.update(
            sg, state.opt_state, state.params, refresh=False,
            projected=True, apply=True, skip_nonfinite=True,
            shard_axes=("data",))
        return TrainState(p2, st2), aux.skipped * jnp.ones((1,), jnp.float32)

    with jax.set_mesh(mesh):
        run = jax.shard_map(body, mesh=mesh, in_specs=(sspec, gspec),
                            out_specs=(sspec, P("data")), check_vma=False)
        out_bad, skipped_bad = run(state, sg_bad)
        out_ok, skipped_ok = run(state, sg_ok)

    # every shard reports the skip, though only shard 0's rows are bad
    np.testing.assert_array_equal(np.asarray(skipped_bad),
                                  np.ones(4, np.float32))
    # params and ALL sharded optimizer state pass through bit-unchanged
    for a, b in zip(jax.tree_util.tree_leaves(state),
                    jax.tree_util.tree_leaves(out_bad)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # control: clean grads apply on every shard
    np.testing.assert_array_equal(np.asarray(skipped_ok),
                                  np.zeros(4, np.float32))
    d = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
        jax.tree_util.tree_leaves(state.params),
        jax.tree_util.tree_leaves(out_ok.params)))
    assert d > 0.0, d
    print("OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=420, env=env,
    )
    assert out.returncode == 0, (
        f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    )
    assert "OK" in out.stdout
