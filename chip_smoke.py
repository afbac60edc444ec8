"""On-chip smoke test of the low-rank training step and the serving path.

Runs the system's main path once through its normal entry points, at the
published widths of Qwen2-1.5B with random weights made from ``--seed``:

* ``train`` -- ``build_model`` + ``make_optimizer("galore-sara-adam",
  engine="bucketed", svd_backend="randomized")`` + ``make_train_step`` +
  ``train_loop``: 8 steps at sequence 4096 (two projector refreshes),
  recovery off, one checkpoint.  Checks finite, descending losses, no
  skipped step, and -- on the chip -- that the compiled hot step holds the
  Pallas ``lowrank_update``, ``galore_project`` and flash-attention kernels
  and the refresh step the ``power_iter`` kernel.
* ``serve`` -- loads that checkpoint through
  ``checkpoint.load_params_latest`` (what ``launch/serve.py --ckpt`` uses)
  and answers 4 requests through ``ContinuousEngine`` (paged KV cache,
  paged decode kernel).  Each request's first decode-step logits are
  compared with the last-position logits of a full forward pass of the
  same params in float32.

``--chips 4`` runs only the multi-chip path instead: the compressed-DP
(``compressed="flat"``) step with ZeRO-sharded optimizer state on a (4, 1)
mesh, against the replicated compressed step.

Usage, from the root of a checkout::

    python3 chip_smoke.py                  # one TPU chip: train + serve
    python3 chip_smoke.py --chips 4        # four chips: ZeRO vs replicated
    JAX_PLATFORMS=cpu python3 chip_smoke.py --cpu-rehearsal   # tiny, CPU

Without a TPU the script exits non-zero unless ``--cpu-rehearsal`` is
given, which runs a tiny configuration on the CPU (kernels take their jnp
references there).  Every check raises; the last line of a passing run is
one JSON object naming the device.  Step times printed here come from a
smoke run of a few steps, not from a benchmark.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import shutil
import statistics
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor

REPO = os.path.dirname(os.path.abspath(__file__))

ARCH = "qwen2-1.5b"
STEPS = 8  # two refreshes at TAU = 4
TAU = 4
LR = 1e-3
# SARA draws the rank-r subspace from a pool of the top POOL x r singular
# directions.  At r = 384 the default pool (4 r = 1536 = d_model) spans the
# whole width, so the randomized SVD needs no power iteration at all; a
# pool of 2 r keeps the sketch (2 r + 8) narrower than d_model, and the
# refresh runs the power-iteration kernel.
POOL = 2
PAGE = 16
REQUESTS = 4
NEW_TOKENS = 8
# Serving runs in bf16 (the configuration's compute dtype); the reference
# forward pass runs in f32 at highest matmul precision.  bf16 keeps 8
# significant bits (relative step 2^-8 = 3.9e-3) and each layer rounds
# ~10 matmul inputs to it, so the logits drift by a few 2^-8 in relative
# L2 norm.  5e-2 (~13 x 2^-8) admits that and still fails on a wrong
# position, page, mask or layer, which move the logits by O(1).
LOGITS_REL_L2_TOL = 5e-2


@dataclasses.dataclass(frozen=True)
class Size:
    layers: int  # kept decoder layers (the rest are cut)
    batch: int
    seq: int
    rank: int
    prompts: tuple  # prompt lengths of the served requests


# One chip (16 GiB): 6 layers at batch 1 x 4096 compile to 13.06 GiB for
# the refresh step (TPU v5e compiler's memory_analysis, arguments + temps +
# unaliased outputs); 7 layers take 14.63 GiB, less than 10% free.
CHIP = Size(layers=6, batch=1, seq=4096, rank=384, prompts=(96, 112, 128, 80))
# Four chips: the compressed step replicates params and state on each chip,
# so the depth is cut further; one sequence per chip.
FOUR = Size(layers=2, batch=4, seq=4096, rank=384, prompts=())
# CPU rehearsal: the registry's reduced qwen2 config.
TINY = Size(layers=2, batch=4, seq=64, rank=8, prompts=(9, 12, 7, 10))


def _bootstrap():
    """Put the checkout's ``src`` on the path and place the compile cache
    before JAX is imported."""
    src = os.path.join(REPO, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SystemExit(
            "chip_smoke.py runs from the root of a checkout of the "
            f"repository: no src/repro next to {__file__}"
        )
    sys.path.insert(0, src)
    from repro.launch.runtime import configure_compile_cache

    return configure_compile_cache()


def _cache_entries(path):
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def _log(phase, msg):
    print(f"[{phase}] {msg}", flush=True)


def _check(cond, msg):
    if not cond:
        raise AssertionError(msg)


class _Timed:
    """Wraps a jitted step: blocks on its outputs and records wall time."""

    def __init__(self, fn):
        self.fn = fn
        self.seconds = []

    def __call__(self, *args, **kwargs):
        import jax

        t0 = time.perf_counter()
        out = jax.block_until_ready(self.fn(*args, **kwargs))
        self.seconds.append(time.perf_counter() - t0)
        return out


def _compile_all(lowered):
    """Compile lowered programs concurrently (XLA compiles off the GIL);
    returns (compiled programs, wall seconds)."""
    t0 = time.perf_counter()
    with ThreadPoolExecutor(len(lowered)) as pool:
        compiled = list(pool.map(lambda low: low.compile(), lowered))
    return compiled, time.perf_counter() - t0


def _families(counts, prefixes):
    return {p: sum(n for k, n in counts.items() if k.startswith(p))
            for p in prefixes}


def _config(size, rehearsal):
    from repro.configs.registry import get_config

    return get_config(ARCH, smoke=rehearsal).with_(n_layers=size.layers)


def _optimizer(params, size, **kw):
    from repro.core import make_optimizer

    return make_optimizer(
        "galore-sara-adam", params, engine="bucketed",
        svd_backend="randomized", rank=size.rank, tau=TAU,
        sara_pool_factor=POOL, lr=LR, **kw,
    )


def phase_train(size, seed, ckpt_dir, on_tpu):
    import jax
    import numpy as np

    from repro.configs.base import TrainConfig
    from repro.configs.registry import get_config
    from repro.data.synthetic import SyntheticDataConfig, SyntheticDataset
    from repro.models import build_model, count_params
    from repro.roofline.analysis import pallas_kernel_counts
    from repro.train.checkpoint import checkpoint_dirs
    from repro.train.loop import train_loop
    from repro.train.state import TrainState
    from repro.train.step import make_train_step

    cfg = _config(size, not on_tpu)
    full = get_config(ARCH)
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    _log("train", f"{ARCH}: d_model {cfg.d_model}, heads {cfg.n_heads}/"
         f"{cfg.n_kv_heads} x {cfg.head_dim}, d_ff {cfg.d_ff}, vocab "
         f"{cfg.vocab_size}, compute {np.dtype(cfg.dtype).name}; layers "
         f"kept {cfg.n_layers} of {full.n_layers} "
         f"({full.n_layers - cfg.n_layers} cut); "
         f"{count_params(params) / 1e9:.3f}B params")
    opt = _optimizer(params, size)
    state = TrainState(params, opt.init(params))
    tc = TrainConfig(
        total_steps=STEPS, checkpoint_every=STEPS, keep_checkpoints=1,
        checkpoint_dir=ckpt_dir, async_checkpoint=False,
    )
    fns = make_train_step(model, opt, train_cfg=tc)
    data = SyntheticDataset(SyntheticDataConfig(
        vocab_size=cfg.vocab_size, seq_len=size.seq,
        global_batch=size.batch, seed=seed,
    ))
    _log("train", f"tokens per step {size.batch * size.seq} "
         f"({size.batch} x {size.seq}); rank {size.rank}, tau {TAU}, "
         f"{STEPS} steps")

    batch0 = data.batch_at(0)
    (hot, refresh), compile_s = _compile_all([
        fns["jit_step"].lower(state, batch0),
        fns["jit_refresh_step"].lower(state, batch0, group=0),
    ])
    _log("train", f"compile seconds (hot + refresh, concurrent) "
         f"{compile_s:.1f}")
    k_hot = pallas_kernel_counts(hot.as_text())
    k_ref = pallas_kernel_counts(refresh.as_text())
    _log("train", f"Pallas kernels, hot step: {k_hot}")
    _log("train", f"Pallas kernels, refresh step: {k_ref}")
    if on_tpu:
        fam = _families(k_hot, ("lowrank_", "galore_project",
                                "flash_attention"))
        _check(all(fam.values()), f"hot step lacks a kernel family: {fam}")
        _check(_families(k_ref, ("power_iter",))["power_iter"] > 0,
               f"refresh step holds no power_iter kernel: {k_ref}")

    fns["jit_step"] = t_hot = _Timed(fns["jit_step"])
    fns["jit_refresh_step"] = t_ref = _Timed(fns["jit_refresh_step"])
    res = train_loop(model, opt, data, tc, fns, state=state, log_every=1,
                     handle_signals=False)
    losses = res.losses
    _log("train", "losses " + " ".join(f"{x:.4f}" for x in losses))
    _check(len(losses) == STEPS and res.final_step == STEPS,
           f"ran {len(losses)} steps to step {res.final_step}, want {STEPS}")
    _check(all(math.isfinite(x) for x in losses), f"non-finite: {losses}")
    _check(losses[-1] < losses[0],
           f"loss did not descend: {losses[0]} -> {losses[-1]}")
    skipped = sum(r.get("skipped", 0.0) for r in res.history)
    _check(skipped == 0, f"{skipped} skipped steps")
    # first call of each executable re-traces; report the later ones
    _log("train", "step wall seconds (smoke, not a benchmark): hot median "
         f"{statistics.median(t_hot.seconds[1:]):.4f} over "
         f"{len(t_hot.seconds) - 1}, refresh {t_ref.seconds[-1]:.4f} "
         f"(step {TAU}); all hot {[round(x, 4) for x in t_hot.seconds]}, "
         f"all refresh {[round(x, 4) for x in t_ref.seconds]}")
    stats = jax.devices()[0].memory_stats() or {}
    _log("train", f"peak_bytes_in_use {stats.get('peak_bytes_in_use')} of "
         f"bytes_limit {stats.get('bytes_limit')}")
    _check(checkpoint_dirs(ckpt_dir), f"no checkpoint under {ckpt_dir}")
    _log("train", f"checkpoint written: {checkpoint_dirs(ckpt_dir)}")
    return model


def phase_serve(model, size, seed, ckpt_dir, on_tpu):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import transformer as tfm
    from repro.roofline.analysis import pallas_kernel_counts
    from repro.serve.engine import ContinuousEngine
    from repro.train.checkpoint import load_params_latest

    cfg = model.cfg
    skeleton = jax.eval_shape(model.init, jax.random.PRNGKey(seed))
    params, step = load_params_latest(ckpt_dir, skeleton)
    _log("serve", f"params restored from checkpoint step {step}")
    eng = ContinuousEngine(
        model, params, max_slots=REQUESTS, page_size=PAGE,
        max_seq_len=max(size.prompts) + NEW_TOKENS + PAGE,
    )
    # the first decode step of each request: logits of the slot whose
    # request has emitted exactly its prefill token
    first = {}
    step_args = []
    paged_step = eng._step

    def spy(*args):
        if not step_args:  # shapes only: the step donates the pool
            step_args.append(jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               sharding=x.sharding), args))
        out = paged_step(*args)
        logits = np.asarray(out[0])
        for slot, st in eng.sched.active_slots():
            if len(st.out_tokens) == 1 and st.req.rid not in first:
                first[st.req.rid] = (st.out_tokens[0], logits[slot])
        return out

    eng._step = spy
    key = jax.random.PRNGKey(seed + 1)
    prompts = {}
    for i, n in enumerate(size.prompts):
        toks = np.asarray(jax.random.randint(
            jax.random.fold_in(key, i), (n,), 0, cfg.vocab_size))
        prompts[eng.submit(toks, NEW_TOKENS, arrival=i)] = toks
    results = eng.run()
    _check(len(results) == REQUESTS, f"{len(results)} of {REQUESTS} served")
    for rid, r in results.items():
        _check(len(r.tokens) == NEW_TOKENS,
               f"request {rid}: {len(r.tokens)} of {NEW_TOKENS} tokens")
    _check(set(first) == set(results), f"first decode steps seen: {first}")

    counts = pallas_kernel_counts(
        paged_step.lower(*step_args[0]).compile().as_text())
    _log("serve", f"Pallas kernels, paged decode step: {counts}")
    if on_tpu:
        _check(counts.get("paged_decode_attention_kernel", 0) > 0,
               f"decode step holds no paged decode kernel: {counts}")

    ref_cfg = cfg.with_(dtype=jnp.float32)

    @jax.jit
    def reference(p, tokens):
        with jax.default_matmul_precision("highest"):
            h, _, _ = tfm.forward_hidden(p, ref_cfg, tokens)
            return h[:, -1] @ tfm.lm_head_matrix(p, ref_cfg)

    worst = 0.0
    for rid in sorted(results):
        tok0, got = first[rid]
        seq = np.concatenate([prompts[rid], [tok0]]).astype(np.int32)
        want = np.asarray(reference(params, jnp.asarray(seq)[None]))[0]
        rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
        worst = max(worst, rel)
        _log("serve", f"request {rid}: prompt {len(prompts[rid])}, tokens "
             f"{results[rid].tokens.tolist()}, first decode-step logits "
             f"rel L2 vs f32 forward {rel:.3e}")
    _check(worst <= LOGITS_REL_L2_TOL,
           f"decode logits off the reference: {worst} > {LOGITS_REL_L2_TOL}")
    _log("serve", f"{REQUESTS} requests, worst rel L2 {worst:.3e} "
         f"<= {LOGITS_REL_L2_TOL}")


def phase_four(size, seed, on_tpu):
    """Compressed-DP with ZeRO-sharded state vs the replicated compressed
    step on a (4, 1) mesh, 6 steps; the comparison rule is the one of
    tests/test_distributed.py::test_zero_sharded_compressed_matches_
    replicated (bit-identical until the second refresh, < 1e-6 after)."""
    import jax
    import numpy as np

    from repro.data.synthetic import SyntheticDataConfig, SyntheticDataset
    from repro.launch import sharding as shd
    from repro.launch.mesh import make_mesh
    from repro.models import build_model
    from repro.train.state import TrainState
    from repro.train.step import make_train_step

    steps = 6
    cfg = _config(size, not on_tpu)
    model = build_model(cfg)
    mesh = make_mesh((4, 1))
    _log("four", f"{ARCH}: d_model {cfg.d_model}, d_ff {cfg.d_ff}, vocab "
         f"{cfg.vocab_size}, layers kept {cfg.n_layers}; "
         f"mesh {dict(mesh.shape)}; batch {size.batch} x {size.seq}; "
         f"compressed='flat', {steps} steps")
    data = SyntheticDataset(SyntheticDataConfig(
        vocab_size=cfg.vocab_size, seq_len=size.seq,
        global_batch=size.batch, seed=seed,
    ))
    key = jax.random.PRNGKey(seed)
    abstract = jax.eval_shape(model.init, key)
    variants = {}
    with jax.set_mesh(mesh):
        batches = [jax.device_put(b, shd.batch_shardings(b, mesh))
                   for b in (data.batch_at(s) for s in range(steps))]
        lowered = []
        for name, kw, zero_axes in (("replicated", {}, None),
                                    ("zero", dict(state_sharding="zero",
                                                  state_shards=4),
                                     ("data",))):
            opt = _optimizer(abstract, size, **kw)
            st = jax.eval_shape(lambda p: TrainState(p, opt.init(p)),
                                abstract)
            sh = (shd.zero_tree_shardings(st, mesh, zero_axes) if zero_axes
                  else shd.tree_shardings(st, mesh))
            st = jax.tree_util.tree_map(
                lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                  sharding=s), st, sh)
            fns = make_train_step(model, opt, mesh=mesh, compressed="flat")
            variants[name] = (opt, sh, fns, zero_axes)
            lowered += [fns["jit_step"].lower(st, batches[0]),
                        fns["jit_refresh_step"].lower(st, batches[0],
                                                      group=0)]
        _, compile_s = _compile_all(lowered)
        _log("four", f"compile seconds (4 programs, concurrent) "
             f"{compile_s:.1f}")

        replicated = []  # host params after each replicated step
        for name, (opt, sh, fns, zero_axes) in variants.items():
            params = jax.jit(model.init)(key)
            state = jax.device_put(TrainState(params, opt.init(params)), sh)
            if zero_axes:
                for x in jax.tree_util.tree_leaves(state.opt_state.buckets):
                    _check(not x.sharding.is_fully_replicated,
                           f"zero stack replicated: {x.sharding}")
            losses, diffs = [], []
            for s in range(steps):
                kind = "jit_refresh_step" if s % TAU == 0 else "jit_step"
                state, m = fns[kind](state, batches[s])
                losses.append(float(m["loss"]))
                host = [np.asarray(x)
                        for x in jax.tree_util.tree_leaves(state.params)]
                if zero_axes is None:
                    replicated.append(host)
                else:
                    diffs.append(max(float(np.max(np.abs(a - b)))
                                     for a, b in zip(replicated[s], host)))
            stats = [d.memory_stats() or {} for d in mesh.devices.flat]
            in_use = [st.get("bytes_in_use", 0) for st in stats]
            _log("four", f"{name}: losses "
                 + " ".join(f"{x:.4f}" for x in losses)
                 + f"; bytes_in_use per device {in_use}")
            if on_tpu:  # the CPU backend keeps no memory stats
                _check(all(b > 2**30 for b in in_use),
                       f"{name}: a device holds < 1 GiB: {in_use}")
            _check(all(math.isfinite(x) for x in losses), f"{losses}")
            del state, m, params
            gc.collect()

    for s, d in enumerate(diffs):
        kind = "refresh" if s % TAU == 0 else "hot"
        _log("four", f"step {s} {kind}: max |zero - replicated| {d:.3e}")
        if s < TAU:
            _check(d == 0.0, f"step {s}: zero != replicated ({d})")
        else:  # second refresh onward: 1-ulp fusion artefact on W'
            _check(d < 1e-6, f"step {s}: zero vs replicated {d} >= 1e-6")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: train + serve on one chip; 4: the ZeRO "
                         "compressed-DP path on a (4, 1) mesh only")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run a tiny configuration on the CPU")
    args = ap.parse_args(argv)
    cache = _bootstrap()
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    on_tpu = platform == "tpu"
    if not on_tpu and not args.cpu_rehearsal:
        raise SystemExit(
            f"no TPU: JAX found {platform} devices; only --cpu-rehearsal "
            "runs without a chip"
        )
    if len(devices) < args.chips:
        raise SystemExit(f"--chips {args.chips} but {len(devices)} devices")
    _log("smoke", f"{len(devices)} x {devices[0].device_kind} "
         f"({platform}); compile cache {cache}: "
         f"{_cache_entries(cache)} entries")
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_four(FOUR if on_tpu else TINY, args.seed, on_tpu)
    else:
        size = CHIP if on_tpu else TINY
        ckpt_dir = tempfile.mkdtemp(prefix="chip_smoke_ckpt_")
        try:
            model = phase_train(size, args.seed, ckpt_dir, on_tpu)
            gc.collect()
            phase_serve(model, size, args.seed, ckpt_dir, on_tpu)
        finally:
            shutil.rmtree(ckpt_dir, ignore_errors=True)
    _log("smoke", f"all phases passed in {time.perf_counter() - t0:.1f}s; "
         f"compile cache {_cache_entries(cache)} entries")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices),
    }}))


if __name__ == "__main__":
    main()
