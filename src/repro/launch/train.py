"""Production training launcher.

Single host (CPU or TPU) or multi-host TPU (via ``jax.distributed.initialize``,
auto-detected from TPU env vars / --coordinator).

    PYTHONPATH=src python -m repro.launch.train --arch llama3-8b \
        --optimizer galore-sara-adam --steps 100 --smoke

``--smoke`` selects the reduced config (CPU-feasible); without it the full
assigned architecture is built (real accelerators).  All fault-tolerance
machinery is live either way: atomic checkpoints, deterministic resume,
straggler monitor, SIGTERM-safe preemption.
"""
from __future__ import annotations

import argparse
import os


def maybe_init_distributed(args) -> None:
    import jax

    if args.coordinator:
        jax.distributed.initialize(
            coordinator_address=args.coordinator,
            num_processes=args.num_processes,
            process_id=args.process_id,
        )
    elif os.environ.get("TPU_WORKER_HOSTNAMES"):
        jax.distributed.initialize()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--optimizer", default="galore-sara-adam")
    ap.add_argument("--steps", type=int, default=1000)
    ap.add_argument("--lr", type=float, default=0.01)
    ap.add_argument("--warmup", type=int, default=100)
    ap.add_argument("--rank", type=int, default=0)
    ap.add_argument("--rank-schedule", default="",
                    help="rank schedule 'kind:start[:floor][@decay_fraction]'"
                         " (e.g. cosine:128:32@0.5): the loop re-buckets at "
                         "refresh boundaries (DESIGN.md §2.12)")
    ap.add_argument("--log-spectrum", action="store_true",
                    help="log the refresh-step update spectrum "
                         "(effective rank) into the history")
    ap.add_argument("--tau", type=int, default=200)
    ap.add_argument("--alpha", type=float, default=0.25)
    ap.add_argument("--seq", type=int, default=0)
    ap.add_argument("--batch", type=int, default=0)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--ckpt-every", type=int, default=200)
    ap.add_argument("--mesh", default="",
                    help="'data,model' e.g. '16,16'; default single device")
    ap.add_argument("--compressed-dp", action="store_true",
                    help="project-then-reduce DP gradient compression")
    ap.add_argument("--engine", default="",
                    help="optimizer engine override: reference | bucketed")
    ap.add_argument("--state-sharding", default="",
                    help="'' (replicated) | 'zero' (DESIGN.md §2.10)")
    ap.add_argument("--state-shards", type=int, default=0,
                    help="ZeRO shard count; default = DP extent of --mesh")
    ap.add_argument("--no-sharded-ckpt", action="store_true",
                    help="force canonical per-leaf checkpoints even for "
                         "zero-sharded state (slow single-writer fallback)")
    ap.add_argument("--refresh-groups", type=int, default=1)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--no-recovery", action="store_true",
                    help="abort on the first fault (pre-recovery behavior)")
    ap.add_argument("--max-rollbacks", type=int, default=3)
    ap.add_argument("--max-bad-steps", type=int, default=3,
                    help="consecutive bad steps before a rollback")
    ap.add_argument("--loss-spike-factor", type=float, default=0.0,
                    help=">0: loss > factor x windowed median is a bad step")
    ap.add_argument("--stale-action", default="log",
                    choices=("log", "rollback", "abort"),
                    help="escalation for a stale worker heartbeat")
    ap.add_argument("--collective-timeout", type=float, default=0.0,
                    help=">0: arm the collective watchdog (per-step sync)")
    ap.add_argument("--heartbeat-timeout", type=float, default=60.0)
    ap.add_argument("--coordinator", default="")
    ap.add_argument("--num-processes", type=int, default=1)
    ap.add_argument("--process-id", type=int, default=0)
    args = ap.parse_args()
    from repro.launch.runtime import configure_compile_cache

    configure_compile_cache()  # before jax is imported
    maybe_init_distributed(args)

    import jax
    import jax.numpy as jnp

    from repro.configs.base import TrainConfig
    from repro.configs.registry import get_config
    from repro.core import make_optimizer
    from repro.core.schedules import cosine_with_warmup
    from repro.data.synthetic import SyntheticDataConfig, SyntheticDataset
    from repro.launch.mesh import axes_size, batch_axes, make_mesh
    from repro.models import build_model, count_params
    from repro.train.loop import train_loop
    from repro.train.monitor import CollectiveWatchdog, HeartbeatRegistry
    from repro.train.recovery import RecoveryPolicy
    from repro.train.state import TrainState
    from repro.train.step import make_train_step, shard_train_state

    cfg = get_config(args.arch, smoke=args.smoke)
    if args.smoke:
        cfg = cfg.with_(dtype=jnp.float32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    print(f"[train] {args.arch} {count_params(params) / 1e6:.1f}M params "
          f"on {jax.device_count()} device(s)")

    # the mesh shape is needed before the optimizer: state_sharding="zero"
    # bakes the shard count into the padded stacks at init
    mesh = None
    if args.mesh:
        shape = tuple(int(x) for x in args.mesh.split(","))
        mesh = make_mesh(shape)

    rank = args.rank or min(512, max(8, cfg.d_model // 4))
    if args.rank_schedule and not args.rank:
        from repro.core.rank_schedule import parse_rank_schedule

        # start at the schedule's step-0 rank; the loop re-buckets from
        # there at refresh boundaries
        rank = parse_rank_schedule(args.rank_schedule).start
    kw = dict(
        lr=args.lr,
        lr_schedule=cosine_with_warmup(args.lr, args.warmup, args.steps),
        grad_clip_norm=1.0,
    )
    if args.engine:
        kw["engine"] = args.engine
    zero_dp_axes = None
    if args.state_sharding:
        kw["state_sharding"] = args.state_sharding
        if args.state_sharding == "zero":
            zero_dp_axes = batch_axes(mesh) if mesh is not None else ()
            shards = args.state_shards or (
                axes_size(mesh, zero_dp_axes) if mesh is not None else 1
            )
            kw["state_shards"] = shards
    if args.optimizer != "adam":
        kw.update(rank=rank, tau=args.tau, alpha=args.alpha,
                  refresh_groups=args.refresh_groups)
        if args.rank_schedule:
            kw["rank_schedule"] = args.rank_schedule
    opt = make_optimizer(args.optimizer, params, **kw)

    seq = args.seq or (64 if args.smoke else 512)
    batch = args.batch or (8 if args.smoke else 512)
    data = SyntheticDataset(SyntheticDataConfig(
        vocab_size=cfg.vocab_size, seq_len=seq, global_batch=batch
    ))

    shardings = None
    state = TrainState(params, opt.init(params))
    if mesh is not None:
        state, shardings = shard_train_state(
            state, mesh, zero_dp_axes=zero_dp_axes or None
        )
    tc = TrainConfig(
        total_steps=args.steps, checkpoint_every=args.ckpt_every,
        checkpoint_dir=args.ckpt_dir, microbatch=args.microbatch,
        sharded_checkpoint=not args.no_sharded_ckpt,
        rank_schedule=args.rank_schedule,
        log_spectrum=args.log_spectrum,
    )
    recovery = None
    if not args.no_recovery:
        recovery = RecoveryPolicy(
            max_bad_steps=args.max_bad_steps,
            loss_spike_factor=args.loss_spike_factor,
            max_rollbacks=args.max_rollbacks,
            rollback_backoff_s=0.5,
            stale_worker_action=args.stale_action,
        )
    heartbeats = HeartbeatRegistry(timeout_s=args.heartbeat_timeout)
    watchdog = None
    if args.collective_timeout > 0:
        watchdog = CollectiveWatchdog(
            timeout_s=args.collective_timeout,
            on_timeout=lambda s, dt: print(
                f"[train] WATCHDOG: step call {s} collectives exceeded "
                f"{dt:.1f}s"
            ),
        )
    fns = make_train_step(
        model, opt, mesh=mesh, train_cfg=tc,
        compressed=args.compressed_dp, recovery=recovery,
        watchdog=watchdog,
    )

    def run():
        return train_loop(
            model, opt, data, tc, fns, state=state, shardings=shardings,
            log_every=max(args.steps // 20, 1),
            recovery=recovery, heartbeats=heartbeats,
            worker_name=f"worker{args.process_id}",
        )

    if mesh is not None:
        with jax.set_mesh(mesh):
            res = run()
    else:
        res = run()
    print(f"[train] done: step {res.final_step}, "
          f"loss {res.losses[0]:.4f} -> {res.losses[-1]:.4f}")
    recs = [r for r in res.history if "skip_steps" in r]
    if recs:
        last = recs[-1]
        events = [r for r in res.history if "event" in r]
        print(f"[train] recovery: {int(last['skip_steps'])} skipped, "
              f"{int(last['rollbacks'])} rollbacks, "
              f"{int(last['save_retries'])} save retries, "
              f"{int(last['save_failures'])} save failures, "
              f"{len(events)} recovery events, "
              f"stale workers: {int(last.get('stale_workers', 0))}")


if __name__ == "__main__":
    main()
