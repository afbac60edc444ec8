if __name__ == "__main__":
    # Must run before any jax import (jax locks the device count at first
    # init) and only when executed as a script: importing this module for
    # its helpers must not clobber the caller's XLA_FLAGS.  The preset
    # appends to pre-existing flags; it never overwrites them.
    from repro.launch.runtime import apply_runtime_preset

    apply_runtime_preset("dryrun")

_DOC = """Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this:
  1. builds the full-size model config and the production mesh
     (16x16 single-pod / 2x16x16 multi-pod),
  2. lowers the appropriate step -- train_step (fwd+bwd+SARA optimizer),
     serve prefill, or serve decode -- against ShapeDtypeStruct inputs with
     the sharding rules applied (no real allocation),
  3. compiles, prints memory_analysis() (proves it fits) and
     cost_analysis() (FLOPs/bytes), parses collective bytes from the HLO,
  4. writes a JSON roofline artifact to experiments/dryrun/.

Usage:
  python -m repro.launch.dryrun --arch llama3-8b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both
"""

import argparse
import json
import os
import sys
import time
import traceback

import jax
import jax.numpy as jnp


def _build_cell(arch: str, shape_name: str, args, mesh=None):
    from repro.configs.base import SHAPES, TrainConfig
    from repro.configs.registry import get_config
    from repro.configs import specs as specs_lib
    from repro.core import make_optimizer
    from repro.launch import sharding as shd
    from repro.models import build_model
    from repro.train.state import TrainState
    from repro.train.step import make_train_step

    # Layers stay SCANNED (honest peak-memory analysis: the unrolled form
    # defeats XLA buffer reuse).  The while-body flop undercount is fixed by
    # compiling twice -- unroll=1 and unroll=2 -- and scaling the measured
    # body delta by (L-1); see run_cell / roofline/analysis.py.
    cfg = get_config(arch).with_(
        scan_layers=True, scan_unroll=args.unroll,
        seq_shard_activations=not args.no_seq_shard,
        ssm_head_tp=args.ssm_head_tp,
    )
    if args.no_attn_tp:
        shd.RULE_OVERRIDES[r"(q_proj|k_proj|v_proj)"] = ("data", None)
        shd.RULE_OVERRIDES[r"o_proj"] = (None, "data")
    if args.ssm_head_tp:
        # keep the fused in_proj out-dim whole so z/x/B/C/dt splits are local
        shd.RULE_OVERRIDES[r"\bin_proj"] = ("data", None)
    if args.attn_impl:
        cfg = cfg.with_(attn_impl=args.attn_impl)
    if args.remat:
        cfg = cfg.with_(remat=args.remat)
    if args.loss_chunk:
        cfg = cfg.with_(loss_chunk=args.loss_chunk)
    if args.attn_chunk_q:
        cfg = cfg.with_(attn_chunk_q=args.attn_chunk_q)
    if args.attn_chunk_kv:
        cfg = cfg.with_(attn_chunk_kv=args.attn_chunk_kv)
    if getattr(args, "ssm_chunk", 0):
        cfg = cfg.with_(ssm_chunk=args.ssm_chunk)
    shape = SHAPES[shape_name]
    model = build_model(cfg)
    params_shape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    total_params = sum(
        x.size for x in jax.tree_util.tree_leaves(params_shape)
    )

    out = {
        "cfg": cfg, "shape": shape, "model": model,
        "params_shape": params_shape, "total_params": total_params,
    }

    if shape.kind == "train":
        rank = args.rank or min(512, max(128, cfg.d_model // 4))
        sched_kw = {}
        if getattr(args, "rank_schedule", ""):
            from repro.core import rank_schedule as rank_schedule_lib

            sched = rank_schedule_lib.parse_rank_schedule(args.rank_schedule)
            if not args.rank:
                # compile the step-0 geometry: the schedule starts here and
                # re-buckets downward at refresh boundaries (DESIGN.md §2.12)
                rank = sched.start
            sched_kw = dict(rank_schedule=args.rank_schedule)
        zero_kw = {}
        if getattr(args, "state_sharding", "") == "zero" and mesh is not None:
            # shard count = DP replica count of the axes the compressed
            # schedule reduces over (all batch axes flat, or just 'pod')
            from repro.launch.mesh import axes_size, batch_axes

            dp = (("pod",) if getattr(args, "compressed_dp", "") == "pod"
                  else batch_axes(mesh))
            # zero shards the bucket stacks, so it implies the
            # bucket-native engine
            zero_kw = dict(state_sharding="zero",
                           state_shards=axes_size(mesh, dp),
                           engine="bucketed")
        opt = make_optimizer(
            args.optimizer, params_shape,
            rank=rank, tau=200, lr=0.01,
            svd_backend="randomized",
            refresh_groups=args.refresh_groups,
            **sched_kw,
            **zero_kw,
        )
        opt_state_shape = jax.eval_shape(opt.init, params_shape)
        state_shape = TrainState(params_shape, opt_state_shape)
        tc = TrainConfig(microbatch=getattr(args, "microbatch", 0))
        fns = make_train_step(
            model, opt, mesh=mesh, train_cfg=tc,
            compressed=(getattr(args, "compressed_dp", "") or False),
            donate=False,
        )
        out.update(
            opt=opt, state_shape=state_shape,
            step_fn=fns["refresh_step" if args.refresh else "step"],
            batch_specs=specs_lib.train_batch_specs(cfg, shape),
        )
    elif shape.kind == "prefill":
        out.update(
            batch_specs=specs_lib.prefill_batch_specs(cfg, shape),
            prefill_fn=lambda p, b: model.prefill(p, b),
        )
    else:  # decode
        out.update(
            batch_specs=specs_lib.decode_batch_specs(cfg, shape),
            cache_shape=specs_lib.decode_cache_specs(model, shape),
            decode_fn=lambda p, c, b: model.decode(p, c, b),
        )
    return out


def _dp_comm_model(cell, mesh=None) -> dict:
    """Modeled per-replica DP gradient-reduction bytes/collectives for the
    reduction schedules of this train cell's optimizer (the bucket plan is
    rebuilt for accounting when the optimizer runs the reference engine).
    With a mesh, the per-axis split (intra-pod vs inter-pod operand bytes)
    and -- for a zero-sharded layout -- the reduce-scatter/all-gather
    schedule and per-device state bytes are included."""
    from repro.core import buckets as buckets_lib

    opt = cell["opt"]
    is_spec = lambda x: hasattr(x, "lowrank")  # noqa: E731
    flat_specs, treedef = jax.tree_util.tree_flatten(
        opt.specs, is_leaf=is_spec
    )
    flat_params = treedef.flatten_up_to(cell["params_shape"])
    plan = opt.bucket_plan or buckets_lib.build_bucket_plan(
        flat_specs, flat_params
    )
    axis_sizes = None
    if mesh is not None:
        axis_sizes = {a: int(mesh.shape[a]) for a in ("pod", "data")
                      if a in mesh.axis_names}
    shards = (opt.state_layout.shards
              if opt.state_layout is not None else 1)
    rank_plans = None
    sched_model = None
    if opt.config.rank_schedule:
        from repro.configs.base import TrainConfig
        from repro.core import rank_schedule as rank_schedule_lib

        sched = rank_schedule_lib.parse_rank_schedule(
            opt.config.rank_schedule
        )
        horizon = sched.total_steps or TrainConfig().total_steps
        rank_plans = rank_schedule_lib.schedule_rank_plans(
            opt.config, cell["params_shape"], sched, total_steps=horizon,
        )
        sched_model = rank_schedule_lib.scheduled_state_model(
            opt.config, cell["params_shape"], sched, total_steps=horizon,
            state_shards=shards,
        )
        sched_model.pop("rank_plans", None)  # BucketPlans: not JSON
    out = buckets_lib.dp_comm_model(
        plan, flat_params, axis_sizes=axis_sizes,
        state_shards=shards, inner=opt.config.inner,
        rank_plans=rank_plans,
    )
    if sched_model is not None:
        # the schedule-aware resident-state trajectory (peak / average /
        # static baseline / per-segment steps) travels with the artifact
        out["rank_schedule"] = sched_model
    return out


def _compile_cell(cell, mesh, args):
    from repro.launch import sharding as shd

    shape = cell["shape"]
    param_sh = shd.tree_shardings(cell["params_shape"], mesh)
    batch_sh = jax.tree_util.tree_map(
        lambda x: jax.NamedSharding(mesh, shd.batch_spec(x.shape, mesh)),
        cell["batch_specs"],
    )
    if shape.kind == "train":
        if getattr(args, "state_sharding", "") == "zero":
            from repro.launch.mesh import batch_axes

            dp = (("pod",) if getattr(args, "compressed_dp", "") == "pod"
                  else batch_axes(mesh))
            state_sh = shd.zero_tree_shardings(cell["state_shape"], mesh, dp)
        else:
            state_sh = shd.tree_shardings(cell["state_shape"], mesh)
        jitted = jax.jit(
            cell["step_fn"], in_shardings=(state_sh, batch_sh),
            donate_argnums=(0,),
        )
        lowered = jitted.lower(cell["state_shape"], cell["batch_specs"])
    elif shape.kind == "prefill":
        jitted = jax.jit(
            cell["prefill_fn"], in_shardings=(param_sh, batch_sh)
        )
        lowered = jitted.lower(cell["params_shape"], cell["batch_specs"])
    else:
        cache_sh = shd.cache_shardings(cell["cache_shape"], mesh)
        jitted = jax.jit(
            cell["decode_fn"],
            in_shardings=(param_sh, cache_sh, batch_sh),
            donate_argnums=(1,),
        )
        lowered = jitted.lower(
            cell["params_shape"], cell["cache_shape"], cell["batch_specs"],
        )
    return lowered.compile()


def _raw_costs(compiled):
    from repro.roofline import analysis as ra

    ca = compiled.cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    coll = ra.collective_stats(compiled.as_text())
    return (
        float(ca.get("flops", 0.0)),
        float(ca.get("bytes accessed", 0.0)),
        float(coll["total_bytes"]),
        coll,
    )


def run_cell(arch: str, shape_name: str, mesh_name: str, args) -> dict:
    from repro.launch.mesh import make_production_mesh
    from repro.roofline import analysis as ra

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    n_chips = mesh.size
    cell = _build_cell(arch, shape_name, args, mesh=mesh)
    cfg, shape, model = cell["cfg"], cell["shape"], cell["model"]
    layers = cfg.n_layers

    n_micro = 1
    if shape.kind == "train" and getattr(args, "microbatch", 0):
        n_micro = max(shape.global_batch // args.microbatch, 1)
    with jax.set_mesh(mesh):
        compiled = _compile_cell(cell, mesh, args)
        t_compile1 = time.time() - t0
        f1, b1, c1, coll1 = _raw_costs(compiled)
        if n_micro > 1:
            # the microbatch while-body (the whole fwd+bwd) is counted once;
            # scale by n_micro (over-counts the optimizer tail by (n-1)x,
            # <0.1% of step flops -- documented)
            f1, b1, c1 = f1 * n_micro, b1 * n_micro, c1 * n_micro
        # Second compile with unroll=2: the measured (u2 - u1) delta is one
        # true loop-body cost; scale by (L-1) to undo the while-body
        # single-count (roofline/analysis.py).  Skip when L < 2.
        body_f = body_b = body_c = 0.0
        if layers >= 2 and not args.single_compile:
            args2 = argparse.Namespace(**vars(args))
            args2.unroll = 2
            cell2 = _build_cell(arch, shape_name, args2, mesh=mesh)
            compiled2 = _compile_cell(cell2, mesh, args)
            f2, b2, c2, _ = _raw_costs(compiled2)
            if n_micro > 1:
                f2, b2, c2 = f2 * n_micro, b2 * n_micro, c2 * n_micro
            body_f = max(f2 - f1, 0.0)
            body_b = max(b2 - b1, 0.0)
            body_c = max(c2 - c1, 0.0)
        t_compile = time.time() - t0 - t_compile1

    layer_corr = {
        "flops": body_f * (layers - 1) * n_chips,  # analyze() divides back
        "bytes": body_b * (layers - 1) * n_chips,
        "n_iters": float(layers),
    }
    mf = ra.model_flops(cfg, shape, cell["total_params"])
    mb = ra.model_bytes(cfg, shape, cell["total_params"])
    corrections = ra.scan_corrections(cfg, shape)
    corrections["layer_scan"] = layer_corr
    # Modeled DP gradient-reduction payload (core/buckets.dp_comm_model):
    # the compressed project-then-reduce schedule's ~d/r traffic saving as
    # a recorded number next to the HLO-measured collective bytes, for all
    # three schedules (standard / compressed hot / compressed refresh).
    dp_comm = None
    if shape.kind == "train":
        dp_comm = _dp_comm_model(cell, mesh)
        dp_comm["requested_mode"] = getattr(args, "compressed_dp", "") or ""
        dp_comm["state_sharding"] = getattr(args, "state_sharding", "") or ""
    report = ra.analyze(
        compiled,
        arch=arch, shape=shape_name, mesh_name=mesh_name, n_chips=n_chips,
        model_flops=mf, corrections=corrections,
        extra={
            "compile1_s": t_compile1, "compile2_s": t_compile,
            "model_bytes": mb,
            "total_params": cell["total_params"],
            "optimizer": args.optimizer if shape.kind == "train" else None,
            "kind": shape.kind,
            "attn_impl": cfg.attn_impl, "remat": cfg.remat,
            "refresh": bool(args.refresh) if shape.kind == "train" else None,
            "variant": args.variant,
            "n_micro": n_micro,
            "collective_bytes_body_corrected": c1 + body_c * (layers - 1),
            "dp_comm_model": dp_comm,
        },
    )
    # Collectives inside the layer loop are also single-counted in the HLO
    # text: apply the measured body correction to the collective term too.
    report = dataclasses_replace_collectives(
        report, c1 + body_c * (layers - 1)
    )
    print(compiled.memory_analysis())
    print({"flops(u1)": f1, "bytes(u1)": b1, "collective(u1)": c1,
           "body_flops": body_f, "body_bytes": body_b,
           "body_collective": body_c})
    return report


def dataclasses_replace_collectives(report, corrected_bytes: float):
    import dataclasses as dc

    from repro.roofline import hw

    return dc.replace(
        report,
        collective_bytes=corrected_bytes,
        collective_term_s=corrected_bytes / hw.ICI_LINK_BW,
        bottleneck=max(
            {
                "compute": report.compute_term_s,
                "memory": report.memory_term_s,
                "collective": corrected_bytes / hw.ICI_LINK_BW,
            }.items(),
            key=lambda kv: kv[1],
        )[0],
    )


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--arch")
    parser.add_argument("--shape")
    parser.add_argument("--mesh", default="single",
                        choices=["single", "multi", "both"])
    parser.add_argument("--all", action="store_true")
    parser.add_argument("--optimizer", default="galore-sara-adam")
    parser.add_argument("--rank", type=int, default=0)
    parser.add_argument("--rank-schedule", default="",
                        help="rank schedule spec 'kind:start[:floor]"
                             "[@decay_fraction]' (e.g. cosine:128:32@0.5): "
                             "builds the step-0 geometry and records the "
                             "schedule-aware memory trajectory (peak/avg "
                             "modeled_state_bytes) in the artifact")
    parser.add_argument("--refresh", action="store_true",
                        help="lower the projector-refresh step instead")
    parser.add_argument("--refresh-groups", type=int, default=1)
    parser.add_argument("--attn-impl", default="")
    parser.add_argument("--remat", default="")
    parser.add_argument("--loss-chunk", type=int, default=0)
    parser.add_argument("--attn-chunk-q", type=int, default=0)
    parser.add_argument("--attn-chunk-kv", type=int, default=0)
    parser.add_argument("--unroll", type=int, default=1)
    parser.add_argument("--single-compile", action="store_true",
                        help="skip the unroll=2 body-cost probe")
    parser.add_argument("--no-seq-shard", action="store_true",
                        help="disable Megatron-SP boundary sharding")
    # --- perf-iteration knobs (§Perf) ---
    parser.add_argument("--no-attn-tp", action="store_true",
                        help="replicate attention projections over `model` "
                             "(for head counts that don't divide TP)")
    parser.add_argument("--ssm-head-tp", action="store_true",
                        help="shard SSD heads over `model`; replicates the "
                             "fused in_proj out-dim so z/x/B/C/dt splits "
                             "stay local")
    parser.add_argument("--compressed-dp", default="",
                        choices=["", "flat", "pod"],
                        help="project-then-reduce gradient compression: "
                             "'flat' = all DP axes manual; 'pod' = only the "
                             "inter-pod axis (hierarchical; FSDP stays auto)")
    parser.add_argument("--state-sharding", default="",
                        choices=["", "zero"],
                        help="'zero' = ZeRO-shard the bucket optimizer "
                             "state over the DP axes (shard count is "
                             "derived from the mesh; DESIGN.md §2.10)")
    parser.add_argument("--ssm-chunk", type=int, default=0,
                        help="SSD chunk length override")
    parser.add_argument("--microbatch", type=int, default=0,
                        help="gradient-accumulation microbatch size "
                             "(activation-memory lever)")
    parser.add_argument("--variant", default="baseline",
                        help="label stored in the artifact (perf iterations)")
    parser.add_argument("--out-dir", default="experiments/dryrun")
    parser.add_argument("--skip-existing", action="store_true")
    args = parser.parse_args(argv)

    from repro.configs.registry import cells

    if args.all:
        todo = [(a, s) for a, s, ok in cells(include_skipped=False)]
    else:
        if not args.arch or not args.shape:
            parser.error("--arch and --shape required unless --all")
        todo = [(args.arch, args.shape)]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    os.makedirs(args.out_dir, exist_ok=True)
    failures = []
    for arch, shape in todo:
        for mesh_name in meshes:
            tag = f"{arch}__{shape}__{mesh_name}"
            if args.variant != "baseline":
                tag += f"__{args.variant}"
            path = os.path.join(args.out_dir, tag + ".json")
            if args.skip_existing and os.path.exists(path):
                print(f"[skip] {tag}")
                continue
            print(f"[dryrun] {tag} ...", flush=True)
            try:
                report = run_cell(arch, shape, mesh_name, args)
                with open(path, "w") as f:
                    f.write(report.to_json())
                print(
                    f"[ok] {tag}: bottleneck={report.bottleneck} "
                    f"compute={report.compute_term_s:.4f}s "
                    f"memory={report.memory_term_s:.4f}s "
                    f"collective={report.collective_term_s:.4f}s "
                    f"useful_ratio={report.useful_ratio:.3f} "
                    f"roofline_frac={report.roofline_fraction():.3f}",
                    flush=True,
                )
            except Exception as e:  # noqa: BLE001
                failures.append((tag, repr(e)))
                traceback.print_exc()
                print(f"[FAIL] {tag}: {e!r}", flush=True)
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for tag, err in failures:
            print(f"  {tag}: {err}")
        return 1
    print("\nall cells compiled OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
