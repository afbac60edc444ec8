"""Distributed behavior on 8 fake CPU devices.

Each test runs in a SUBPROCESS with --xla_force_host_platform_device_count=8
so the main pytest process keeps its single-device view (the dry-run rule:
only dryrun.py forces device counts).
"""
import os
import subprocess
import sys
import textwrap

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(body: str, timeout=420):
    code = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.registry import get_config
        from repro.configs.specs import concrete_train_batch
        from repro.models import build_model
        from repro.core import make_optimizer
        from repro.launch.mesh import make_mesh
        from repro.launch import sharding as shd
        from repro.train.state import TrainState
        from repro.train.step import make_train_step, shard_train_state
        """
    ) + textwrap.dedent(body)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=timeout, env=env,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_sharded_train_step_runs_and_matches_single_device():
    out = run_sub("""
    cfg = get_config("llama3-8b", smoke=True).with_(dtype=jnp.float32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = make_optimizer("galore-sara-adam", params, rank=8, tau=5, lr=1e-3)
    state = TrainState(params, opt.init(params))
    batch = concrete_train_batch(cfg, 8, 32)
    # single-device result
    fns0 = make_train_step(model, opt, donate=False)
    s0, m0 = fns0["jit_step"](state, batch)
    mesh = make_mesh((4, 2))
    with jax.set_mesh(mesh):
        st, _ = shard_train_state(state, mesh)
        bsh = jax.device_put(batch, shd.batch_shardings(batch, mesh))
        fns = make_train_step(model, opt, mesh=mesh, donate=False)
        s1, m1 = fns["jit_step"](st, bsh)
    d = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
        jax.tree_util.tree_leaves(s0.params),
        jax.tree_util.tree_leaves(s1.params)))
    assert d < 1e-4, d
    print("OK", d)
    """)
    assert "OK" in out


def test_compressed_dp_equals_standard():
    # The compressed step is one shard_map, manual over every mesh axis
    # (train/step.py compressed_step_fn).
    out = run_sub("""
    cfg = get_config("llama3-8b", smoke=True).with_(dtype=jnp.float32,
                                                    n_layers=2)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = make_optimizer("galore-sara-adam", params, rank=8, tau=5, lr=1e-3)
    state = TrainState(params, opt.init(params))
    batch = concrete_train_batch(cfg, 8, 32)
    mesh = make_mesh((4, 2))
    with jax.set_mesh(mesh):
        st, _ = shard_train_state(state, mesh)
        bsh = jax.device_put(batch, shd.batch_shardings(batch, mesh))
        s1, _ = make_train_step(model, opt, mesh=mesh,
                                donate=False)["jit_step"](st, bsh)
        s2, _ = make_train_step(model, opt, mesh=mesh, compressed=True,
                                donate=False)["jit_step"](st, bsh)
    d = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
        jax.tree_util.tree_leaves(s1.params),
        jax.tree_util.tree_leaves(s2.params)))
    assert d < 1e-5, d
    print("OK", d)
    """)
    assert "OK" in out


def test_compression_reduces_dp_allreduce_bytes():
    """project-then-reduce must shrink the DP gradient collectives in HLO."""
    out = run_sub("""
    from repro.roofline.analysis import collective_stats
    cfg = get_config("llama3-8b", smoke=True).with_(
        dtype=jnp.float32, n_layers=2, d_model=256, n_heads=4, head_dim=64,
        n_kv_heads=2, d_ff=512)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = make_optimizer("galore-sara-adam", params, rank=8, tau=5, lr=1e-3,
                         min_dim=64)
    state = TrainState(params, opt.init(params))
    batch = concrete_train_batch(cfg, 8, 32)
    mesh = make_mesh((8, 1))  # pure DP so all collectives are grad syncs
    sizes = {}
    with jax.set_mesh(mesh):
        ssh = shd.tree_shardings(state, mesh)
        bsh = shd.batch_shardings(batch, mesh)
        for name, comp in (("std", False), ("cmp", True)):
            fns = make_train_step(model, opt, mesh=mesh, compressed=comp,
                                  donate=False)
            c = jax.jit(fns["step"], in_shardings=(ssh, bsh)).lower(
                state, batch).compile()
            sizes[name] = collective_stats(c.as_text())["total_bytes"]
    print("std", sizes["std"], "cmp", sizes["cmp"])
    assert sizes["cmp"] < 0.8 * sizes["std"], sizes
    print("OK")
    """)
    assert "OK" in out


def test_moe_ep_equals_local_on_mesh():
    out = run_sub("""
    from repro.models import moe as moe_lib
    cfg = get_config("deepseek-moe-16b", smoke=True).with_(
        dtype=jnp.float32, moe_capacity_factor=8.0)
    key = jax.random.PRNGKey(0)
    p = moe_lib.init_moe_mlp(key, cfg)
    x = jax.random.normal(jax.random.fold_in(key, 1),
                          (4, 16, cfg.d_model)) * 0.5
    out_local, _ = moe_lib._apply_moe_local(p, x, cfg)
    mesh = make_mesh((2, 4))
    with jax.set_mesh(mesh):
        out_ep, _ = jax.jit(lambda p_, x_: moe_lib.apply_moe_mlp(
            p_, x_, cfg))(p, x)
    err = float(jnp.max(jnp.abs(out_local - out_ep)))
    assert err < 1e-4, err
    print("OK", err)
    """)
    assert "OK" in out


def test_elastic_restore_1_to_8_devices(tmp_path):
    """Checkpoint saved unsharded on 1 device restores sharded on 8."""
    ckpt = str(tmp_path / "elastic")
    # save on a single device (subprocess without forced device count)
    code_save = f"""
import jax, jax.numpy as jnp
from repro.configs.registry import get_config
from repro.models import build_model
from repro.core import make_optimizer
from repro.train.state import TrainState
from repro.train.checkpoint import CheckpointManager
cfg = get_config("llama3-8b", smoke=True).with_(dtype=jnp.float32)
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
opt = make_optimizer("galore-sara-adam", params, rank=8)
state = TrainState(params, opt.init(params))
CheckpointManager({ckpt!r}, keep=1).save(state, 5)
print("SAVED")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", code_save], capture_output=True, text=True,
        timeout=300, env=env,
    )
    assert out.returncode == 0, out.stderr
    out = run_sub(f"""
    from repro.train.checkpoint import CheckpointManager
    cfg = get_config("llama3-8b", smoke=True).with_(dtype=jnp.float32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = make_optimizer("galore-sara-adam", params, rank=8)
    skeleton = TrainState(params, opt.init(params))
    mesh = make_mesh((4, 2))
    with jax.set_mesh(mesh):
        sh = shd.tree_shardings(skeleton, mesh)
        restored = CheckpointManager({ckpt!r}, keep=1).load(
            skeleton, shardings=sh)
    for a, b in zip(jax.tree_util.tree_leaves(skeleton.params),
                    jax.tree_util.tree_leaves(restored.params)):
        assert a.shape == b.shape
    # restored params match the originals bit-for-bit
    d = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
        jax.tree_util.tree_leaves(params),
        jax.tree_util.tree_leaves(restored.params)))
    assert d == 0.0, d
    print("OK")
    """)
    assert "OK" in out


def test_production_mesh_shapes():
    out = run_sub("""
    # can't build 512 devices here; validate the mesh spec logic instead
    from repro.launch.mesh import make_mesh, batch_axes
    m = make_mesh((4, 2))
    assert m.axis_names == ("data", "model")
    assert batch_axes(m) == ("data",)
    m3 = make_mesh((2, 2, 2), ("pod", "data", "model"))
    assert batch_axes(m3) == ("pod", "data")
    print("OK")
    """)
    assert "OK" in out


def test_compressed_parity_matrix_bucketed():
    """ISSUE 4 matrix: engine=bucketed x mode {flat, pod} x {hot, refresh}
    on a 4-device mesh, fp32.

    Two claims per cell:
    * the stacked (bucket-native) reduction is BIT-FOR-BIT with the
      per-leaf reference-engine reduction -- psum is elementwise, so
      reducing one (B, r, n)/(B, d, n) stack per bucket must change
      nothing vs reducing the ragged leaf tree;
    * vs the UNCOMPRESSED step the hot cell agrees to 1e-5 (reduction
      order differs at fp32 last-bit), and the refresh cell to 1e-3 --
      the randomized-SVD + Gumbel-top-k chain squares the spectrum and
      amplifies those last-bit gradient differences.
    """
    out = run_sub("""
    cfg = get_config("llama3-8b", smoke=True).with_(dtype=jnp.float32,
                                                    n_layers=2)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = concrete_train_batch(cfg, 8, 32)

    def maxdiff(a, b):
        return max(float(jnp.max(jnp.abs(x - y))) for x, y in zip(
            jax.tree_util.tree_leaves(a.params),
            jax.tree_util.tree_leaves(b.params)))

    kw = dict(rank=8, tau=5, lr=1e-3, svd_backend="randomized")
    for mode, mesh_shape, axes in (
        ("flat", (2, 2), ("data", "model")),
        ("pod", (2, 2, 1), ("pod", "data", "model")),
    ):
        mesh = make_mesh(mesh_shape, axes)
        opt_b = make_optimizer("galore-sara-adam", params,
                               engine="bucketed", **kw)
        opt_r = make_optimizer("galore-sara-adam", params,
                               engine="reference", **kw)
        assert opt_b.state_layout is not None  # stacked psum payload
        with jax.set_mesh(mesh):
            st_b, _ = shard_train_state(
                TrainState(params, opt_b.init(params)), mesh)
            st_r, _ = shard_train_state(
                TrainState(params, opt_r.init(params)), mesh)
            bsh = jax.device_put(batch, shd.batch_shardings(batch, mesh))
            fstd = make_train_step(model, opt_b, mesh=mesh, donate=False)
            fcmp = make_train_step(model, opt_b, mesh=mesh,
                                   compressed=mode, donate=False)
            fref = make_train_step(model, opt_r, mesh=mesh,
                                   compressed=mode, donate=False)
            assert fcmp["compressed_mode"] == mode
            for kind, tol in (("jit_step", 1e-5),
                              ("jit_refresh_step", 1e-3)):
                s_cmp, _ = fcmp[kind](st_b, bsh)
                s_ref, _ = fref[kind](st_r, bsh)
                d_bit = maxdiff(s_cmp, s_ref)
                assert d_bit == 0.0, (mode, kind, d_bit)
                s_std, _ = fstd[kind](st_b, bsh)
                d_std = maxdiff(s_cmp, s_std)
                assert d_std < tol, (mode, kind, d_std)
                print("cell", mode, kind, d_bit, d_std)
    print("OK")
    """)
    assert "OK" in out


def test_compressed_resume_crosses_engines(tmp_path):
    """A checkpoint written mid-run by the compressed bucketed path resumes
    under the uncompressed reference engine (canonical layout on disk) and
    training continues within the per-step DP tolerance."""
    ckpt = str(tmp_path / "cross")
    out = run_sub(f"""
    from repro.train.checkpoint import CheckpointManager
    from repro.train.state import checkpoint_converters
    cfg = get_config("llama3-8b", smoke=True).with_(dtype=jnp.float32,
                                                    n_layers=2)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = concrete_train_batch(cfg, 8, 32)
    mesh = make_mesh((2, 2))

    def maxdiff(a, b):
        return max(float(jnp.max(jnp.abs(x - y))) for x, y in zip(
            jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)))

    kw = dict(rank=8, tau=5, lr=1e-3, svd_backend="randomized")
    opt_b = make_optimizer("galore-sara-adam", params, engine="bucketed",
                           **kw)
    opt_r = make_optimizer("galore-sara-adam", params, engine="reference",
                           **kw)
    with jax.set_mesh(mesh):
        bsh = jax.device_put(batch, shd.batch_shardings(batch, mesh))
        # compressed bucketed run: refresh + hot step, then checkpoint
        st, _ = shard_train_state(TrainState(params, opt_b.init(params)),
                                  mesh)
        fcmp = make_train_step(model, opt_b, mesh=mesh, compressed="flat",
                               donate=False)
        s, _ = fcmp["jit_refresh_step"](st, bsh)
        s, _ = fcmp["jit_step"](s, bsh)
        can, loc = checkpoint_converters(opt_b)
        mgr = CheckpointManager({ckpt!r}, keep=1, canonicalize=can,
                                localize=loc)
        mgr.save(s, 2)
        # resume A: UNCOMPRESSED under the reference engine (canonical
        # layout on disk loads without conversion)
        skel_r = TrainState(params, opt_r.init(params))
        res_r = CheckpointManager({ckpt!r}, keep=1).load(
            skel_r, shardings=shd.tree_shardings(skel_r, mesh))
        # the checkpointed params resume bit-for-bit
        d0 = maxdiff(res_r.params, s.params)
        assert d0 == 0.0, d0
        fstd = make_train_step(model, opt_r, mesh=mesh, donate=False)
        cA, _ = fstd["jit_step"](res_r, bsh)
        # resume B: COMPRESSED bucketed again (localize converts back to
        # the storage layout)
        skel_b = TrainState(params, opt_b.init(params))
        res_b = mgr.load(skel_b)  # localize -> storage layout
        cB, _ = fcmp["jit_step"](res_b, bsh)
        # one hot step after the crossing: compressed vs uncompressed
        # continuations agree to the hot-step DP tolerance
        d1 = maxdiff(cA.params, cB.params)
        assert d1 < 1e-5, d1
    print("OK", d0, d1)
    """)
    assert "OK" in out


def test_zero_sharded_compressed_matches_replicated():
    """ISSUE 7 e2e: compressed flat mode with state_sharding='zero' on a
    (4, 2) mesh -- bucket stacks physically sharded along the DP axis, the
    hot step reduce-scatters the R-space stacks instead of all-reducing,
    and the trajectory matches the replicated-state compressed run.

    Tolerance note: the first two steps and every hot step before the
    SECOND refresh are bit-identical.  From the second refresh on (the
    first with nonzero moments), XLA fuses the zero program's entry
    all-gather into the moment-transport einsum differently than the
    replicated program, reassociating one contraction: W' picks up a 1-ulp
    (~1.5e-8) difference while every piece of optimizer state stays
    bit-identical.  Bit-exactness of the sharded update itself is pinned
    by the single-process matrix in test_update_engine.py."""
    out = run_sub("""
    cfg = get_config("llama3-8b", smoke=True).with_(dtype=jnp.float32,
                                                    n_layers=2)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = concrete_train_batch(cfg, 8, 32)
    mesh = make_mesh((4, 2))

    def maxdiff(a, b):
        return max(float(jnp.max(jnp.abs(x - y))) for x, y in zip(
            jax.tree_util.tree_leaves(a.params),
            jax.tree_util.tree_leaves(b.params)))

    kw = dict(rank=8, tau=3, lr=1e-3, svd_backend="randomized",
              engine="bucketed")
    opt_r = make_optimizer("galore-sara-adam", params, **kw)
    opt_z = make_optimizer("galore-sara-adam", params,
                           state_sharding="zero", state_shards=4, **kw)
    with jax.set_mesh(mesh):
        bsh = jax.device_put(batch, shd.batch_shardings(batch, mesh))
        st_r, _ = shard_train_state(TrainState(params, opt_r.init(params)),
                                    mesh)
        st_z, _ = shard_train_state(TrainState(params, opt_z.init(params)),
                                    mesh, zero_dp_axes=("data",))
        # the bucket stacks are physically sharded along the DP axis
        for x in jax.tree_util.tree_leaves(st_z.opt_state.buckets):
            assert not x.sharding.is_fully_replicated, x.sharding
        f_r = make_train_step(model, opt_r, mesh=mesh, compressed="flat",
                              donate=False)
        f_z = make_train_step(model, opt_z, mesh=mesh, compressed="flat",
                              donate=False)
        assert f_z["state_sharding"] == "zero"
        assert f_r["state_sharding"] == ""
        # shard count must match the DP extent of the mesh
        opt_bad = make_optimizer("galore-sara-adam", params,
                                 state_sharding="zero", state_shards=8,
                                 **kw)
        try:
            make_train_step(model, opt_bad, mesh=mesh, compressed="flat",
                            donate=False)
            raise AssertionError("mismatched state_shards not rejected")
        except ValueError as e:
            assert "state_shards" in str(e), e
        # the zero hot step reduce-scatters; the replicated one does not
        jx_z = str(jax.make_jaxpr(f_z["step"])(st_z, bsh))
        jx_r = str(jax.make_jaxpr(f_r["step"])(st_r, bsh))
        has_rs = lambda s: ("reduce_scatter" in s) or ("reduce-scatter" in s)
        assert has_rs(jx_z), "no reduce-scatter in the zero hot step"
        assert not has_rs(jx_r)
        for step in range(5):
            refresh = step % 3 == 0
            kind = "jit_refresh_step" if refresh else "jit_step"
            st_r, _ = f_r[kind](st_r, bsh)
            st_z, _ = f_z[kind](st_z, bsh)
            d = maxdiff(st_r, st_z)
            if step < 3:
                assert d == 0.0, (step, d)
            else:  # second refresh onward: 1-ulp fusion artifact on W'
                assert d < 1e-6, (step, d)
            print("step", step, "refresh" if refresh else "hot", d)

    # pod mode: zero shards over the 'pod' axis only (shards=2), intra-pod
    # (data, model) stays auto -- one refresh + one hot step, bit-identical
    # to the replicated pod-mode run
    mesh_p = make_mesh((2, 2, 2), ("pod", "data", "model"))
    opt_zp = make_optimizer("galore-sara-adam", params,
                            state_sharding="zero", state_shards=2, **kw)
    with jax.set_mesh(mesh_p):
        bsh = jax.device_put(batch, shd.batch_shardings(batch, mesh_p))
        st_r, _ = shard_train_state(TrainState(params, opt_r.init(params)),
                                    mesh_p)
        st_z, _ = shard_train_state(TrainState(params, opt_zp.init(params)),
                                    mesh_p, zero_dp_axes=("pod",))
        f_r = make_train_step(model, opt_r, mesh=mesh_p, compressed="pod",
                              donate=False)
        f_z = make_train_step(model, opt_zp, mesh=mesh_p, compressed="pod",
                              donate=False)
        assert "reduce_scatter" in str(jax.make_jaxpr(f_z["step"])(st_z,
                                                                   bsh))
        st_r, _ = f_r["jit_refresh_step"](st_r, bsh)
        st_z, _ = f_z["jit_refresh_step"](st_z, bsh)
        d0 = maxdiff(st_r, st_z)
        st_r, _ = f_r["jit_step"](st_r, bsh)
        st_z, _ = f_z["jit_step"](st_z, bsh)
        d1 = maxdiff(st_r, st_z)
        assert d0 == 0.0 and d1 == 0.0, (d0, d1)
        print("pod", d0, d1)
    print("OK")
    """)
    assert "OK" in out


def test_compressed_step_psums_one_operand_per_bucket():
    """jaxpr verification of the ISSUE 4 acceptance criterion: the
    compressed step's DP reduction carries ONE contiguous operand per
    bucket -- (B, r, n) R-space stacks hot, (B, d, n) full stacks on
    refresh -- and NO per-leaf low-rank payload crosses the wire."""
    out = run_sub("""
    from repro.core import projectors as proj_lib
    cfg = get_config("llama3-8b", smoke=True).with_(dtype=jnp.float32,
                                                    n_layers=2)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = make_optimizer("galore-sara-adam", params, rank=8, tau=5,
                         lr=1e-3, engine="bucketed")
    state = TrainState(params, opt.init(params))
    batch = concrete_train_batch(cfg, 8, 32)
    mesh = make_mesh((4, 2))

    def psum_operands(jaxpr, out):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "psum":
                out.extend(tuple(v.aval.shape) for v in eqn.invars)
            for val in eqn.params.values():
                vals = val if isinstance(val, (list, tuple)) else [val]
                for v in vals:
                    inner = getattr(v, "jaxpr", None)
                    if hasattr(v, "eqns"):
                        psum_operands(v, out)
                    elif inner is not None and hasattr(inner, "eqns"):
                        psum_operands(inner, out)
        return out

    is_spec = lambda x: hasattr(x, "lowrank")
    flat_specs, treedef = jax.tree_util.tree_flatten(opt.specs,
                                                     is_leaf=is_spec)
    flat_params = treedef.flatten_up_to(params)
    perleaf_rspace = set()
    for spec, p in zip(flat_specs, flat_params):
        if spec.lowrank:  # the ragged per-leaf shapes the old path psum'd
            perleaf_rspace.add(tuple(
                jax.eval_shape(lambda g: proj_lib.project(
                    g, jnp.zeros(p.shape[:-2] + (
                        min(p.shape[-2], p.shape[-1]), spec.rank)),
                    spec.side), p).shape))
            perleaf_rspace.add(tuple(p.shape))  # old refresh payload

    plan = opt.bucket_plan
    with jax.set_mesh(mesh):
        fns = make_train_step(model, opt, mesh=mesh, compressed="flat",
                              donate=False)
        for refresh in (False, True):
            fn = fns["refresh_step" if refresh else "step"]
            shapes = psum_operands(jax.make_jaxpr(fn)(state, batch).jaxpr,
                                   [])
            from collections import Counter
            want = Counter(
                (bk.batch, bk.d, bk.n) if refresh
                else (bk.batch, bk.rank, bk.n)
                for bk in plan.buckets
            )
            got = Counter(shapes)
            for shape, n in want.items():
                assert got[shape] == n, (refresh, shape, shapes)
            leaked = [s for s in shapes if s in perleaf_rspace]
            assert not leaked, (refresh, leaked)
            print("psum operands", "refresh" if refresh else "hot",
                  len(shapes))
    print("OK")
    """)
    assert "OK" in out
