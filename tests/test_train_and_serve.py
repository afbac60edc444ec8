"""Training-loop integration: loss descends, resume is deterministic,
preemption checkpointing, subspace tracking; serving engine."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import TrainConfig
from repro.configs.registry import get_config
from repro.core import make_optimizer
from repro.data.synthetic import SyntheticDataConfig, SyntheticDataset
from repro.models import build_model
from repro.serve.engine import ServeEngine
from repro.train.loop import train_loop
from repro.train.state import TrainState
from repro.train.step import make_train_step


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("llama3-8b", smoke=True).with_(dtype=jnp.float32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = make_optimizer(
        "galore-sara-adam", params, rank=8, tau=10, lr=2e-3
    )
    data = SyntheticDataset(
        SyntheticDataConfig(
            vocab_size=cfg.vocab_size, seq_len=64, global_batch=8
        )
    )
    return cfg, model, opt, data


def test_loss_descends_toward_entropy_floor(setup, tmp_path):
    cfg, model, opt, data = setup
    tc = TrainConfig(
        total_steps=40, checkpoint_every=0, lr=2e-3,
        checkpoint_dir=str(tmp_path / "c1"),
    )
    fns = make_train_step(model, opt, donate=False)
    res = train_loop(
        model, opt, data, tc, fns, log_every=20, handle_signals=False
    )
    assert res.losses[-1] < res.losses[0] - 0.5
    floor = data.bigram_entropy()
    assert res.losses[-1] > floor - 0.5  # sanity: can't beat the floor


def test_deterministic_resume(setup, tmp_path):
    cfg, model, opt, data = setup
    ckpt = str(tmp_path / "c2")
    tc = TrainConfig(
        total_steps=24, checkpoint_every=8, checkpoint_dir=ckpt, lr=2e-3,
        async_checkpoint=False,
    )
    fns = make_train_step(model, opt, donate=False)
    res1 = train_loop(
        model, opt, data, tc, fns, log_every=100, handle_signals=False
    )
    # re-run: restores from step 24... but 24 was the end; drop last ckpt to
    # force a mid-run resume instead
    import shutil

    shutil.rmtree(os.path.join(ckpt, "step_00000024"))
    res2 = train_loop(
        model, opt, data, tc, fns, log_every=100, handle_signals=False
    )
    # steps 16..23 rerun; losses must match the first run exactly
    np.testing.assert_allclose(
        np.asarray(res1.losses[16:]), np.asarray(res2.losses), atol=1e-6
    )


def test_bucketed_loop_resumes_across_engines(setup, tmp_path):
    """train_loop with engine='bucketed': checkpoints serialize the
    canonical layout, and a reference-engine loop resumes the bucketed
    run's checkpoint with identical losses (and vice versa)."""
    cfg, model, _, data = setup
    params = model.init(jax.random.PRNGKey(0))
    ckpt = str(tmp_path / "cx")
    tc = TrainConfig(
        total_steps=12, checkpoint_every=4, checkpoint_dir=ckpt, lr=2e-3,
        async_checkpoint=False,
    )

    def run(engine):
        opt = make_optimizer(
            "galore-sara-adam", params, rank=8, tau=4, lr=2e-3,
            engine=engine,
        )
        fns = make_train_step(model, opt, donate=False)
        return train_loop(
            model, opt, data, tc, fns, log_every=100, handle_signals=False
        )

    res_b = run("bucketed")  # steps 0..11, checkpoints at 4, 8, 12
    import shutil

    shutil.rmtree(os.path.join(ckpt, "step_00000012"))
    res_r = run("reference")  # resumes from the bucketed step-8 checkpoint
    np.testing.assert_allclose(
        np.asarray(res_b.losses[8:]), np.asarray(res_r.losses), atol=1e-6
    )
    shutil.rmtree(os.path.join(ckpt, "step_00000012"))
    res_b2 = run("bucketed")  # and back: bucketed resumes reference's save
    np.testing.assert_allclose(
        np.asarray(res_b.losses[8:]), np.asarray(res_b2.losses), atol=1e-6
    )


class _ProbeLoss:
    """Records WHEN (at which loop step) the device->host fetch happens."""

    def __init__(self, value, step, log, now):
        self.value = value
        self.step = step
        self._log = log
        self._now = now

    def __float__(self):
        # now[0] is the NEXT step index by flush time (the producing step
        # already incremented it), so the current loop step is now[0] - 1
        self._log.append((self.step, self._now[0] - 1))
        return self.value


def test_loop_fetches_metrics_at_log_cadence(setup, tmp_path):
    """The loop must not force a device->host sync every step: losses are
    fetched in batches at log_every / refresh / final steps, and the
    observable outputs (losses list, order, history recs) are identical
    to per-step fetching."""
    cfg, model, opt, data = setup  # opt: tau=10, refresh_groups=1
    total, log_every = 12, 5
    tc = TrainConfig(
        total_steps=total, checkpoint_every=0,
        checkpoint_dir=str(tmp_path / "cad"),
    )
    conversions = []  # (step whose loss was fetched, step at fetch time)
    now = [0]

    def fake_step(state, batch, group=0):
        m = {"loss": _ProbeLoss(1.0 + now[0], now[0], conversions, now)}
        st = TrainState(state.params, state.opt_state._replace(
            step=state.opt_state.step + 1))
        now[0] += 1
        return st, m

    params = model.init(jax.random.PRNGKey(0))
    state = TrainState(params, opt.init(params))
    fns = {"jit_step": fake_step, "jit_refresh_step": fake_step}
    res = train_loop(
        model, opt, data, tc, fns, state=state, log_every=log_every,
        handle_signals=False,
    )
    # observable behavior identical to per-step fetching
    assert res.losses == [1.0 + s for s in range(total)]
    assert [r["step"] for r in res.history] == [0.0, 5.0, 10.0, 11.0]
    assert [r["loss"] for r in res.history] == [1.0, 6.0, 11.0, 12.0]
    # every fetch happened at a flush step (log / refresh / final), and
    # most steps were NOT fetched at their own step -- no per-step sync
    sub_tau = 10  # tau=10, one group
    assert len(conversions) == total
    for fetched_step, at_step in conversions:
        assert fetched_step <= at_step
        assert (
            at_step % log_every == 0
            or at_step % sub_tau == 0
            or at_step == total - 1
        ), (fetched_step, at_step)
    deferred = sum(1 for s, at in conversions if at > s)
    assert deferred >= total // 2  # the buffer really defers


def test_loop_nan_sentinel_still_aborts(setup, tmp_path):
    """Deferred fetching keeps the NaN abort: it raises at the batched
    fetch point instead of the bad step, counters unchanged."""
    cfg, model, opt, data = setup
    tc = TrainConfig(
        total_steps=30, checkpoint_every=0,
        checkpoint_dir=str(tmp_path / "nan"),
    )

    def nan_step(state, batch, group=0):
        return state, {"loss": jnp.asarray(float("nan"))}

    params = model.init(jax.random.PRNGKey(0))
    state = TrainState(params, opt.init(params))
    fns = {"jit_step": nan_step, "jit_refresh_step": nan_step}
    with pytest.raises(FloatingPointError):
        train_loop(
            model, opt, data, tc, fns, state=state, log_every=3,
            handle_signals=False,
        )


def test_subspace_tracking(setup, tmp_path):
    cfg, model, opt, data = setup
    tc = TrainConfig(
        total_steps=21, checkpoint_every=0,
        checkpoint_dir=str(tmp_path / "c3"),
    )
    fns = make_train_step(model, opt, donate=False)
    res = train_loop(
        model, opt, data, tc, fns, log_every=100, handle_signals=False,
        track_subspace=True,
    )
    summary = res.subspace.summary()
    assert summary, "no overlap series collected"
    for name, vals in summary.items():
        if "adjacent_mean" in vals:
            assert 0.0 <= vals["adjacent_mean"] <= 1.0 + 1e-6
    # the step's own reading, ||P_new^T P_old||^2 / r, in the history of
    # the logged refresh steps (tau 10: steps 0 and 20) and of no other
    recs = [r for r in res.history if "refresh_overlap" in r]
    assert [r["step"] for r in recs] == [0.0, 20.0]
    for r in recs:
        assert 0.0 <= r["refresh_overlap"] <= 1.0 + 1e-6


def test_serving_greedy_deterministic(setup):
    cfg, model, opt, data = setup
    params = model.init(jax.random.PRNGKey(0))
    eng = ServeEngine(model, params, capacity=96)
    batch = {"tokens": data.batch_at(0)["tokens"][:, :16]}
    out1 = eng.generate(batch, max_new_tokens=6)
    out2 = eng.generate(batch, max_new_tokens=6)
    np.testing.assert_array_equal(
        np.asarray(out1.tokens), np.asarray(out2.tokens)
    )
    assert out1.tokens.shape == (8, 6)


def test_serving_sampled(setup):
    cfg, model, opt, data = setup
    params = model.init(jax.random.PRNGKey(0))
    eng = ServeEngine(model, params, capacity=96)
    batch = {"tokens": data.batch_at(0)["tokens"][:, :16]}
    out = eng.generate(
        batch, max_new_tokens=4, greedy=False, temperature=1.0,
        key=jax.random.PRNGKey(7),
    )
    assert np.asarray(out.tokens).max() < cfg.vocab_size


def test_microbatched_step_equals_full_batch(setup):
    """Gradient accumulation: 2 microbatches == single batch (fp32)."""
    cfg, model, opt, data = setup
    params = model.init(jax.random.PRNGKey(0))
    st = TrainState(params, opt.init(params))
    batch = data.batch_at(0)
    full = make_train_step(model, opt, donate=False)
    micro = make_train_step(
        model, opt, donate=False,
        train_cfg=TrainConfig(microbatch=4),
    )
    s1, m1 = full["jit_step"](st, batch)
    s2, m2 = micro["jit_step"](st, batch)
    for a, b in zip(
        jax.tree_util.tree_leaves(s1.params),
        jax.tree_util.tree_leaves(s2.params),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


# ---------------------------------------------------------------------------
# ISSUE 4 satellites: microbatch accumulation + compressed-kwarg hygiene
# ---------------------------------------------------------------------------


def test_microbatch_non_divisible_batch_raises(setup):
    """batch % microbatch != 0 must raise, not silently drop samples --
    but microbatch >= batch (lossless degenerate: one microbatch) stays
    allowed, e.g. a production microbatch meeting a smoke batch."""
    cfg, model, opt, data = setup
    params = model.init(jax.random.PRNGKey(0))
    st = TrainState(params, opt.init(params))
    batch = data.batch_at(0)  # global batch 8
    fns = make_train_step(
        model, opt, donate=False, train_cfg=TrainConfig(microbatch=3),
    )
    with pytest.raises(ValueError, match="not divisible"):
        fns["jit_step"](st, batch)
    big = make_train_step(
        model, opt, donate=False, train_cfg=TrainConfig(microbatch=16),
    )
    full = make_train_step(model, opt, donate=False)
    s_big, _ = big["jit_step"](st, batch)
    s_full, _ = full["jit_step"](st, batch)
    d = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
        jax.tree_util.tree_leaves(s_big.params),
        jax.tree_util.tree_leaves(s_full.params)))
    assert d < 2e-5, d


def test_microbatch_accum_dtype_matches_unaccumulated():
    """Accumulated grads come back in the PARAM dtype (bf16 params ->
    bf16 grads, like the non-accumulated path), while partial sums stay
    in the configurable accum dtype."""
    from types import SimpleNamespace

    from repro.train.step import _value_and_grad

    def loss(params, batch):
        h = batch["x"].astype(params["w"].dtype) @ params["w"]
        return jnp.mean(jnp.square(h.astype(jnp.float32))), {}

    model = SimpleNamespace(loss=loss)
    params = {"w": (jnp.ones((4, 4)) * 0.5).astype(jnp.bfloat16)}
    batch = {"x": jax.random.normal(jax.random.PRNGKey(0), (8, 4))}

    (_, _), g_single = _value_and_grad(model, 0)(params, batch)
    (_, _), g_accum = _value_and_grad(model, 2)(params, batch)
    assert g_single["w"].dtype == jnp.bfloat16
    assert g_accum["w"].dtype == jnp.bfloat16  # was f32 before the fix
    np.testing.assert_allclose(
        np.asarray(g_accum["w"], np.float32),
        np.asarray(g_single["w"], np.float32),
        atol=0.05,  # bf16 quantization of per-microbatch grads
    )
    # f32 accumulation beats bf16 accumulation at approximating the
    # full-batch f32 gradient
    params32 = {"w": jnp.ones((4, 4)) * 0.5}
    (_, _), g32 = _value_and_grad(model, 0)(params32, batch)
    (_, _), acc32 = _value_and_grad(model, 2, jnp.float32)(params32, batch)
    (_, _), acc16 = _value_and_grad(model, 2, jnp.bfloat16)(params32, batch)
    assert acc32["w"].dtype == acc16["w"].dtype == jnp.float32
    e32 = float(jnp.max(jnp.abs(acc32["w"] - g32["w"])))
    e16 = float(jnp.max(jnp.abs(acc16["w"] - g32["w"])))
    assert e32 <= e16


def test_compressed_kwarg_normalization(setup):
    from repro.launch.mesh import make_mesh, single_device_mesh

    cfg, model, opt, data = setup
    mesh = single_device_mesh()
    # legacy bool normalizes to 'flat' in one place
    fns = make_train_step(model, opt, mesh=mesh, compressed=True,
                          donate=False)
    assert fns["compressed_mode"] == "flat"
    pod_mesh = make_mesh((1, 1, 1))
    fns = make_train_step(model, opt, mesh=pod_mesh, compressed="pod",
                          donate=False)
    assert fns["compressed_mode"] == "pod"
    # 'pod' mode on a pod-less mesh is rejected at BUILD time
    with pytest.raises(ValueError, match="pod axis"):
        make_train_step(model, opt, mesh=mesh, compressed="pod",
                        donate=False)
    for off in (False, None, ""):
        fns = make_train_step(model, opt, mesh=mesh, compressed=off,
                              donate=False)
        assert fns["compressed_mode"] == ""
    # a typo must raise, not fall through to the flat-DP axis set
    with pytest.raises(ValueError, match="pods"):
        make_train_step(model, opt, mesh=mesh, compressed="pods",
                        donate=False)
    # compressed modes need a mesh to shard over
    with pytest.raises(ValueError, match="mesh"):
        make_train_step(model, opt, compressed="flat", donate=False)


# ---------------------------------------------------------------------------
# ISSUE 10: continuous-batching serve engine (paged KV cache + satellites)
# ---------------------------------------------------------------------------


def _family_batch(cfg, b, s, key):
    batch = {"tokens": jax.random.randint(key, (b, s), 0, cfg.vocab_size)}
    if cfg.family == "vlm":
        batch["patch_embeds"] = jax.random.normal(
            jax.random.fold_in(key, 1), (b, 4, cfg.d_model)
        )
    if cfg.family == "audio":
        batch["frame_embeds"] = jax.random.normal(
            jax.random.fold_in(key, 2), (b, cfg.enc_frames, cfg.d_model)
        )
    return batch


@pytest.mark.serve
def test_continuous_engine_matches_static_tokens(setup):
    """Paged continuous batching with mid-flight arrivals emits exactly the
    tokens static-batch greedy generate produces per request."""
    from repro.serve.engine import ContinuousEngine

    cfg, model, opt, data = setup
    params = model.init(jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(5)
    prompts = [
        np.asarray(jax.random.randint(
            jax.random.fold_in(key, i), (5 + 3 * i,), 0, cfg.vocab_size
        ))
        for i in range(4)
    ]
    new = [6, 4, 7, 5]
    eng = ServeEngine(model, params, capacity=64)
    ref = [
        np.asarray(
            eng.generate({"tokens": jnp.asarray(p)[None]},
                         max_new_tokens=n).tokens
        )[0]
        for p, n in zip(prompts, new)
    ]
    # 2 slots for 4 requests: request 2/3 queue and admit mid-flight as
    # earlier sequences retire
    ce = ContinuousEngine(model, params, max_slots=2, max_seq_len=64,
                          page_size=8)
    rids = [
        ce.submit(p, n, arrival=a)
        for p, n, a in zip(prompts, new, [0, 0, 1, 2])
    ]
    res = ce.run()
    for rid, expect in zip(rids, ref):
        np.testing.assert_array_equal(res[rid].tokens, expect)
    # retirement really freed pages: pool drained back to empty
    assert ce.kv.allocator.used_pages == 0
    assert max(ce.occupancy_trace) > 0


@pytest.mark.serve
def test_continuous_engine_page_accounting(setup):
    """Admission reserves ceil((prompt+max_new)/ps) pages, retirement
    returns them, and over-budget requests are rejected at submit."""
    from repro.serve.engine import ContinuousEngine
    from repro.serve.kv_cache import pages_needed

    cfg, model, opt, data = setup
    params = model.init(jax.random.PRNGKey(0))
    ce = ContinuousEngine(model, params, max_slots=2, max_seq_len=32,
                          page_size=8)
    with pytest.raises(ValueError, match="max_seq_len"):
        ce.submit(np.zeros((30,), np.int32), 10)  # 40 > 32 capacity
    # degenerate requests rejected at submit (max_new=0 used to reach
    # alloc(0), whose -0 slice drained the whole free list)
    with pytest.raises(ValueError, match="degenerate"):
        ce.submit(np.zeros((4,), np.int32), 0)
    with pytest.raises(ValueError, match="degenerate"):
        ce.submit(np.zeros((0,), np.int32), 4)  # empty prompt
    rid = ce.submit(np.zeros((9,), np.int32), 4)  # 13 tokens -> 2 pages
    assert pages_needed(13, 8) == 2
    res = ce.run()
    assert ce.kv.allocator.used_pages == 0
    assert len(res[rid].tokens) == 4


@pytest.mark.serve
def test_continuous_engine_no_overadmission(setup):
    """Contended-pool admission: 6 free pages, two requests needing 5
    pages each.  Both fit individually but not together -- the engine must
    admit one, queue the other until retirement frees its pages, and still
    produce static-identical tokens (the old free_pages check admitted
    both and crashed on the unbacked second reservation)."""
    from repro.serve.engine import ContinuousEngine

    cfg, model, opt, data = setup
    params = model.init(jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(11)
    prompts = [
        np.asarray(jax.random.randint(
            jax.random.fold_in(key, i), (14,), 0, cfg.vocab_size
        ))
        for i in range(2)
    ]
    # page_size=4, max_seq_len=20 -> 5 pages/slot; num_pages=7 -> 6 usable
    ce = ContinuousEngine(model, params, max_slots=2, max_seq_len=20,
                          page_size=4, num_pages=7)
    rids = [ce.submit(p, 4, arrival=0) for p in prompts]  # 18 tok: 5 pages
    res = ce.run()
    first, second = res[rids[0]], res[rids[1]]
    assert second.admit_tick > first.admit_tick  # waited for the pool
    assert ce.kv.allocator.used_pages == 0
    eng = ServeEngine(model, params, capacity=64)
    for p, r in zip(prompts, (first, second)):
        expect = np.asarray(eng.generate(
            {"tokens": jnp.asarray(p)[None]}, max_new_tokens=4
        ).tokens)[0]
        np.testing.assert_array_equal(r.tokens, expect)
    # tick convention: prefill occupies the admit tick, first decode lands
    # the next tick -- every inter-token gap is >= 1 (no 0-gap pairs that
    # would deflate the replay benchmark's p50/p99)
    for r in (first, second):
        assert r.token_ticks[0] == r.admit_tick
        assert (np.diff(r.token_ticks) >= 1).all()


@pytest.mark.serve
@pytest.mark.parametrize(
    "arch", ["llama3-8b", "olmoe-1b-7b", "llava-next-34b", "mamba2-370m",
             "hymba-1.5b", "whisper-medium"],
)
def test_prefill_decode_matches_full_forward(arch):
    """Per family: prefill(prompt) + teacher-forced decode steps reproduce
    the full-sequence forward's last-token logits."""
    from repro.configs.registry import get_config
    from repro.models import build_model

    cfg = get_config(arch, smoke=True).with_(dtype=jnp.float32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(3)
    s, extra = 6, 3
    full = _family_batch(cfg, 1, s + extra, key)
    prompt = {k: (v[:, :s] if k == "tokens" else v) for k, v in full.items()}
    # the KV prefix includes the vlm patch embeddings
    prefix = full["patch_embeds"].shape[1] if cfg.family == "vlm" else 0
    cap = None if cfg.family == "ssm" else prefix + s + extra + 2
    logits_full, _ = (
        model.prefill(params, full)
        if cfg.family == "ssm" else model.prefill(params, full, cap)
    )
    logits, cache = (
        model.prefill(params, prompt)
        if cfg.family == "ssm" else model.prefill(params, prompt, cap)
    )
    for i in range(extra):
        tok = full["tokens"][:, s + i][:, None]
        logits, cache = model.decode(params, cache, {"token": tok})
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(logits_full), atol=2e-4, rtol=1e-4
    )


@pytest.mark.serve
def test_serve_capacity_validation_raises(setup):
    """The silent ring-wrap bug: prompt + max_new_tokens > capacity must
    raise with the required capacity, not wrap and overwrite the prompt."""
    cfg, model, opt, data = setup
    params = model.init(jax.random.PRNGKey(0))
    batch = {"tokens": data.batch_at(0)["tokens"][:1, :12]}
    eng = ServeEngine(model, params, capacity=16)
    with pytest.raises(ValueError, match="capacity=20"):
        eng.generate(batch, max_new_tokens=8)
    # default capacity == prompt length: any decode would wrap
    eng0 = ServeEngine(model, params)
    with pytest.raises(ValueError, match="capacity=13"):
        eng0.generate(batch, max_new_tokens=1)
    # exactly enough passes
    out = ServeEngine(model, params, capacity=20).generate(
        batch, max_new_tokens=8
    )
    assert np.asarray(out.tokens).shape == (1, 8)


@pytest.mark.serve
def test_serve_eos_early_exit(setup):
    """With eos_id, generate stops decoding once every row finished and
    pads the remaining columns with eos."""
    cfg, model, opt, data = setup
    params = model.init(jax.random.PRNGKey(0))
    tok_row = data.batch_at(0)["tokens"][:1, :10]
    batch = {"tokens": jnp.concatenate([tok_row, tok_row], axis=0)}
    eng = ServeEngine(model, params, capacity=64)
    base = np.asarray(eng.generate(batch, max_new_tokens=8).tokens)
    eos = int(base[0, 2])  # both rows identical -> both finish at step 2
    out = eng.generate(batch, max_new_tokens=8, eos_id=eos)
    got = np.asarray(out.tokens)
    assert got.shape == (2, 8)
    np.testing.assert_array_equal(got[:, :3], base[:, :3])
    assert (got[:, 3:] == eos).all()  # padded, not resampled
    assert out.steps < 8  # decode really stopped early


@pytest.mark.serve
def test_scheduler_fcfs_head_of_line():
    from repro.serve.scheduler import Request, Scheduler

    sched = Scheduler(max_slots=2)
    for rid in range(3):
        sched.submit(Request(rid=rid, tokens=np.zeros(4, np.int32),
                             max_new_tokens=2, arrival=0))
    # head request unaffordable: nothing admits behind it
    assert sched.try_admit(0, lambda r, s: r.rid != 0) == []
    admitted = sched.try_admit(0, lambda r, s: True)
    assert [st.req.rid for st in admitted] == [0, 1]  # slots exhausted
    sched.retire(admitted[0].slot, 5, "eos")
    assert [st.req.rid for st in sched.try_admit(5, lambda r, s: True)] == [2]


@pytest.mark.serve
def test_scheduler_reserve_inside_admission_loop():
    """The over-admission race: two heads that each fit individually but
    not together must not both admit in one try_admit call -- the reserve
    callback's grant must be visible to the next head's check."""
    from repro.serve.scheduler import Request, Scheduler

    sched = Scheduler(max_slots=2)
    for rid in range(2):
        sched.submit(Request(rid=rid, tokens=np.zeros(4, np.int32),
                             max_new_tokens=2, arrival=0))
    budget = {"free": 6}  # pool of 6 pages, each request needs 5

    def reserve(req, slot):
        if budget["free"] < 5:
            return False
        budget["free"] -= 5
        return True

    admitted = sched.try_admit(0, reserve)
    assert [st.req.rid for st in admitted] == [0]  # second head must wait
    assert budget["free"] == 1  # exactly one reservation landed


@pytest.mark.serve
def test_page_allocator_reuse_and_double_free():
    from repro.serve.kv_cache import PageAllocator

    alloc = PageAllocator(num_pages=5)  # pages 1..4
    a = alloc.alloc(3)
    assert alloc.alloc(2) is None  # only 1 left: all-or-nothing
    alloc.free(a)
    assert alloc.free_pages == 4
    assert alloc.alloc(0) == []  # -0 slice pitfall: must not drain the pool
    assert alloc.free_pages == 4
    b = alloc.alloc(4)
    assert sorted(b) == [1, 2, 3, 4] and 0 not in b  # trash page never given
    alloc.free(b)
    with pytest.raises(ValueError, match="double free"):
        alloc.free([b[0]])


@pytest.mark.serve
def test_load_params_latest_walks_past_corruption(setup, tmp_path):
    """Train->serve handoff: params come from the newest checkpoint whose
    param leaves verify; a corrupted newest falls back to the previous."""
    from repro.train.checkpoint import CheckpointManager, load_params_latest

    cfg, model, opt, data = setup
    params1 = model.init(jax.random.PRNGKey(1))
    params2 = model.init(jax.random.PRNGKey(2))
    mgr = CheckpointManager(str(tmp_path / "ck"), keep=3)
    mgr.save(TrainState(params1, opt.init(params1)), step=1)
    mgr.save(TrainState(params2, opt.init(params2)), step=2)
    loaded, step = load_params_latest(str(tmp_path / "ck"), params1)
    assert step == 2
    np.testing.assert_array_equal(
        np.asarray(loaded["embed"]), np.asarray(params2["embed"])
    )
    # corrupt the newest step's embed leaf -> fallback to step 1
    victim = tmp_path / "ck" / "step_00000002" / "_params_embed.npy"
    victim.write_bytes(b"corrupt" + victim.read_bytes()[7:])
    loaded, step = load_params_latest(str(tmp_path / "ck"), params1)
    assert step == 1
    np.testing.assert_array_equal(
        np.asarray(loaded["embed"]), np.asarray(params1["embed"])
    )
