"""The training loop: checkpoint/restart, preemption handling, straggler
monitoring, staggered projector refresh, subspace diagnostics, the
degrade-and-recover runtime (skip-step / rollback-and-resample), and the
rank-elastic engine (DESIGN.md §2.12): when the optimizer carries a
``rank_schedule``, refresh boundaries evaluate the schedule host-side and
a rank change triggers a re-bucket event -- rebuild at the new rank,
migrate live state losslessly through the canonical layout, re-jit, and
rebind the checkpoint manager; manifests carry the rank so resume across
a rank boundary rebuilds the right geometry first.

Deterministic resume: data batches are pure functions of the step index and
optimizer RNG lives in the checkpointed state, so a killed-and-restarted run
re-produces the uninterrupted run bit-for-bit (tested).

Recovery (DESIGN.md §2.9): with a :class:`repro.train.recovery
.RecoveryPolicy` the loop never aborts on the first fault.  Non-finite
gradients are gated out inside the compiled step (skip-step; the update is
compiled with the per-bucket finite check when the policy asks for it --
``make_train_step(..., recovery=...)``).  Sustained divergence -- detected
at the metric fetch points by :class:`DivergenceDetector` -- triggers a
rollback: reload the newest checkpoint that verifies
(``CheckpointManager.load_latest`` walks past corrupt ones), fold the
attempt counter into the refresh RNG so stochastic selection methods draw a
fresh subspace, truncate host-side records to the rollback point, and
continue; ``max_rollbacks`` bounds the budget before the classic
``FloatingPointError`` abort.  Checkpoint save failures are retried by the
manager and, under recovery, counted instead of fatal.  Fault injection for
all of this lives in ``train/faults.py`` (a ``FaultPlan`` passes hooks and
a checkpoint-I/O shim through the same seams).

Profiling: each iteration is a ``jax.profiler.StepTraceAnnotation("train")``
holding host spans on the profiler's clock -- ``repro.loop.data`` (the
batch and its hooks), ``.dispatch`` (the step call), ``.fetch`` (the metric
fetch, the loop's device sync), ``.checkpoint``, ``.rebucket`` and
``.spectrum``.  The step time in ``history`` is taken at each fetch, over
the steps it completed; ``compiles`` / ``compile_s`` count the process's
backend compiles since the loop began.
"""
from __future__ import annotations

import dataclasses
import signal
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from repro.configs.base import RankSchedule, TrainConfig
from repro.core import lowrank as lowrank_lib
from repro.core import metrics as metrics_lib
from repro.core import rank_schedule as rank_schedule_lib
from repro.train import checkpoint as ckpt_lib
from repro.train import recovery as recovery_lib
from repro.train.monitor import HeartbeatRegistry, SpectrumLogger, StepMonitor
from repro.train import state as state_lib
from repro.train.state import TrainState

PyTree = Any
# host spans on the profiler's clock (``repro.loop.*``); with the profiler
# off each costs about a microsecond
_span = jax.profiler.TraceAnnotation


@dataclasses.dataclass
class TrainResult:
    state: TrainState
    history: List[Dict[str, Any]]
    final_step: int
    losses: List[float]


class _PreemptionGuard:
    """SIGTERM/SIGINT -> finish the current step, checkpoint, exit cleanly."""

    _SIGNALS = (signal.SIGTERM, signal.SIGINT)

    def __init__(self, enable: bool):
        self.requested = False
        self._prev: Dict[int, Any] = {}
        if enable:
            for sig in self._SIGNALS:
                try:
                    self._prev[sig] = signal.signal(sig, self._handler)
                except ValueError:
                    break  # not on main thread (tests) -- applies to both

    def _handler(self, signum, frame):
        self.requested = True

    def restore(self):
        for sig, prev in self._prev.items():
            signal.signal(sig, prev)


def train_loop(
    model,
    optimizer: lowrank_lib.LowRankOptimizer,
    data,
    train_cfg: TrainConfig,
    step_fns: Dict[str, Callable],
    *,
    state: Optional[TrainState] = None,
    mesh=None,
    shardings: Optional[PyTree] = None,
    log_every: int = 50,
    eval_fn: Optional[Callable[[TrainState, int], Dict[str, float]]] = None,
    track_subspace: bool = False,
    handle_signals: bool = True,
    batch_hook: Optional[Callable] = None,
    recovery: Optional[recovery_lib.RecoveryPolicy] = None,
    fault_plan=None,  # Optional[repro.train.faults.FaultPlan]
    heartbeats: Optional[HeartbeatRegistry] = None,
    worker_name: str = "worker0",
) -> TrainResult:
    tau = max(optimizer.config.tau, 1)
    groups = max(optimizer.config.refresh_groups, 1)
    # Checkpoints serialize the canonical per-leaf state layout by
    # default; bucket-native optimizers convert on save/load
    # (train/state.py).  A ZeRO-sharded run instead writes the
    # shard-parallel format (DESIGN.md §2.11): each process serializes
    # only its own row blocks, no canonical gather on the save path.
    canonicalize, localize = state_lib.checkpoint_converters(optimizer)
    layout = optimizer.state_layout
    shard_spec = None
    if (
        getattr(train_cfg, "sharded_checkpoint", True)
        and layout is not None
        and layout.shards > 1
    ):
        shard_spec = ckpt_lib.ShardSpec(
            num_shards=layout.shards,
            shard_ids=ckpt_lib.local_shard_ids(layout.shards),
        )
    manager = ckpt_lib.CheckpointManager(
        train_cfg.checkpoint_dir, keep=train_cfg.keep_checkpoints,
        canonicalize=canonicalize, localize=localize,
        io=fault_plan.checkpoint_io() if fault_plan is not None else None,
        shard_spec=shard_spec,
        canonical_rows=state_lib.bucket_canonical_rows(optimizer),
    )
    monitor = StepMonitor()
    guard = _PreemptionGuard(handle_signals)
    tracker = metrics_lib.OverlapTracker() if track_subspace else None
    detector = (
        recovery_lib.DivergenceDetector(recovery)
        if recovery is not None else None
    )

    # ---- rank-elastic engine (DESIGN.md §2.12) ----
    # Active only when the optimizer carries a schedule AND the step-fn
    # bundle can re-jit itself at a new bucket geometry (make_train_step's
    # "rebuild" hook; absent for hand-rolled step fns in tests).  The
    # schedule is evaluated HOST-SIDE at refresh boundaries only: rank
    # changes array shapes, so it can never live inside the compiled step.
    rank_sched: Optional[RankSchedule] = None
    if optimizer.config.rank_schedule and "rebuild" in step_fns:
        rank_sched = RankSchedule.parse(optimizer.config.rank_schedule)
    spectrum: Optional[SpectrumLogger] = None
    if getattr(train_cfg, "log_spectrum", False) or (
        rank_sched is not None and rank_sched.kind == "adaptive"
    ):
        # the adaptive policy consumes the probe's effective rank, so it
        # forces the logger on even when spectrum history is not requested
        spectrum = SpectrumLogger(optimizer.specs)

    def _ckpt_meta() -> Optional[Dict[str, Any]]:
        """Schedule state carried in the checkpoint manifest: the rank(s)
        this save's bucket geometry was built at, so resume rebuilds the
        same shapes before loading."""
        if rank_sched is None:
            return None
        r, gr = lowrank_lib.current_ranks(optimizer)
        return {"rank": int(r), "group_ranks": [int(g) for g in gr]}

    def _adopt_optimizer(new_opt: lowrank_lib.LowRankOptimizer) -> None:
        """Swap in an optimizer rebuilt at a new rank: re-jitted step fns,
        refreshed checkpoint converters, manager rebound to the new bucket
        geometry.  ``shardings`` described the OLD bucket shapes, so it is
        dropped -- restore falls back to name-based placements from the
        mesh when one is present."""
        nonlocal optimizer, step_fns, canonicalize, localize, layout
        nonlocal shardings
        optimizer = new_opt
        step_fns = step_fns["rebuild"](new_opt)
        canonicalize, localize = state_lib.checkpoint_converters(new_opt)
        layout = new_opt.state_layout
        manager.rebind(
            canonicalize, localize,
            canonical_rows=state_lib.bucket_canonical_rows(new_opt),
        )
        shardings = None

    def _load_one(skel: TrainState, ck_step: Optional[int] = None):
        """One checkpoint -> (state, step): shardings describe the
        in-memory (storage) layout; with layout converters active the
        serialized (canonical) tree differs, so derive name-based
        shardings for the canonical tree (leaves are loaded directly
        sharded -- elastic restore) and re-place the converted
        storage-layout state afterwards with the CALLER's shardings (the
        zero placements for a ZeRO run, name-based otherwise).  Sharded-
        format checkpoints load straight into the storage layout, so the
        caller shardings place them directly (``storage_shardings``).
        ``ck_step=None`` walks to the newest checkpoint that verifies."""
        if canonicalize is None:
            if ck_step is None:
                return manager.load_latest(skel, shardings=shardings)
            return manager.load(skel, ck_step, shardings=shardings), ck_step
        load_shardings = None
        if shardings is not None and mesh is not None:
            from repro.launch import sharding as shd_lib

            canon_skel = jax.eval_shape(canonicalize, skel)
            load_shardings = shd_lib.tree_shardings(canon_skel, mesh)
        if ck_step is None:
            loaded, ck_step = manager.load_latest(
                skel, shardings=load_shardings, storage_shardings=shardings
            )
        else:
            loaded = manager.load(
                skel, ck_step, shardings=load_shardings,
                storage_shardings=shardings,
            )
        if shardings is not None:
            loaded = jax.tree_util.tree_map(
                jax.device_put, loaded, shardings
            )
        elif mesh is not None:
            from repro.launch import sharding as shd_lib

            loaded = jax.tree_util.tree_map(
                jax.device_put, loaded, shd_lib.tree_shardings(loaded, mesh)
            )
        return loaded, ck_step

    def _restore_latest(skel: TrainState):
        """Newest VERIFYING checkpoint -> (state, step).

        With a rank schedule active the walk is rank-aware: each
        candidate's manifest meta names the rank(s) its bucket geometry
        was built at, and ``load`` demands exact shapes -- so the
        optimizer is rebuilt (and the step fns re-jitted, the manager
        rebound) at the CHECKPOINT's rank before the load skeleton is
        built.  A candidate whose meta or payload fails to read falls
        through to the next-older one, preserving ``load_latest``'s
        walk-past-corruption contract across rank boundaries."""
        if rank_sched is None:
            return _load_one(skel)
        last_err: Optional[Exception] = None
        for ck in reversed(ckpt_lib.checkpoint_dirs(train_cfg.checkpoint_dir)):
            try:
                meta = ckpt_lib.checkpoint_meta(
                    train_cfg.checkpoint_dir, ck
                )
                rank_now, groups_now = lowrank_lib.current_ranks(optimizer)
                want_rank = int(meta.get("rank", rank_now))
                want_groups = tuple(
                    int(g) for g in meta.get("group_ranks", ())
                ) or groups_now
                if (want_rank, want_groups) != (rank_now, groups_now):
                    if len(set(want_groups)) > 1:
                        new_opt = lowrank_lib.rebuild_at_rank(
                            optimizer, skel.params,
                            group_ranks=want_groups,
                        )
                    else:
                        new_opt = lowrank_lib.rebuild_at_rank(
                            optimizer, skel.params, rank=want_rank
                        )
                    _adopt_optimizer(new_opt)
                    skel = TrainState(
                        skel.params, optimizer.init(skel.params)
                    )
                return _load_one(skel, ck)
            except (OSError, ValueError, KeyError) as e:
                last_err = e
                continue
        if last_err is not None:
            raise last_err
        raise FileNotFoundError(
            f"no loadable checkpoint under {train_cfg.checkpoint_dir!r}"
        )

    # ---- init / restore ----
    if state is None:
        params = model.init(jax.random.PRNGKey(train_cfg.seed))
        state = TrainState(params, optimizer.init(params))
    start_step = 0
    if ckpt_lib.checkpoint_dirs(train_cfg.checkpoint_dir):
        state, start_step = _restore_latest(state)
    history: List[Dict[str, Any]] = []
    losses: List[float] = []
    loss_base = start_step  # losses[i] is the loss of step loss_base + i

    def _drain_save_error() -> None:
        """Surface (or, under recovery, count) a failed async save."""
        try:
            manager.wait()
        except Exception as e:
            monitor.save_failures += 1
            if recovery is None:
                raise
            history.append({
                "event": "save_failed", "error": repr(e),
                "rollbacks": float(monitor.rollbacks),
            })
        finally:
            monitor.save_retries = manager.retries_performed

    def _safe_save(cur_state, s: int, blocking: bool) -> None:
        _drain_save_error()  # an old failure must not eat THIS save
        try:
            with _span("repro.loop.checkpoint"):
                manager.save(
                    cur_state, s, blocking=blocking, meta=_ckpt_meta()
                )
        except Exception as e:
            monitor.save_failures += 1
            if recovery is None:
                raise
            history.append({
                "event": "save_failed", "step": float(s), "error": repr(e),
            })
        finally:
            monitor.save_retries = manager.retries_performed

    # Rollback needs a target: with recovery on and an empty checkpoint
    # dir, pin the initial state as step-``start_step`` (save ordinal 0).
    if (
        recovery is not None
        and ckpt_lib.latest_step(train_cfg.checkpoint_dir) is None
    ):
        _safe_save(state, start_step, blocking=True)

    # Per-step metrics stay ON DEVICE between fetch points: ``float(m)``
    # forces a device->host sync every step, serializing dispatch against
    # the accelerator.  Steps buffer (step, device_metrics, health) here
    # and one batched fetch drains the buffer at log_every cadence (and at
    # refresh / checkpoint / preemption / final steps, keeping the buffer
    # small and the checkpoint-adjacent history consistent).  ``losses``
    # and ``history`` come out identical to the per-step fetch -- only the
    # moment the NaN sentinel (or the divergence detector) can raise moves
    # to the fetch point.
    pending: List = []  # (step, device metrics dict, refreshed)

    def _flush_metrics(cur_state, swallow_aborts=False):
        if not pending:
            return
        with _span("repro.loop.fetch"):
            # the newest step's metrics are ready once the device has run
            # every pending step: this sync ends their wall time
            jax.block_until_ready(pending[-1][1])
            health = monitor.end_step(pending[-1][0], steps=len(pending))
            _drain_pending(cur_state, health, swallow_aborts)

    def _drain_pending(cur_state, health, swallow_aborts):
        # drains entry-by-entry so an abort (or rollback trigger) mid-flush
        # never re-processes (or drops) already-fetched losses; the
        # finally-path flush swallows instead of masking an in-flight
        # exception
        while pending:
            s, m, refreshed = pending.pop(0)
            loss = float(m["loss"])
            skipped = (
                float(np.asarray(m["skipped"])) if "skipped" in m else 0.0
            )
            # the psum'd cross-process verdict (train/step.py): identical
            # on every process, so feeding it to the detector makes the
            # rollback decision lockstep across the fleet
            verdict = (
                float(np.asarray(m["bad_step"])) >= 1.0
                if "bad_step" in m else False
            )
            losses.append(loss)
            if skipped >= 1.0:
                monitor.skip_steps += 1
            if detector is None:
                try:
                    monitor.note_loss(s, loss)
                except FloatingPointError:
                    if not swallow_aborts:
                        raise
            else:
                # recovery owns the abort decision: the sentinel only
                # keeps its counters, the detector raises RollbackNeeded
                monitor.note_loss(s, loss, raise_on_streak=False)
                try:
                    detector.observe(
                        s, loss, skipped=skipped >= 1.0, verdict=verdict
                    )
                except recovery_lib.RollbackNeeded:
                    if not swallow_aborts:
                        raise
            if s % log_every == 0 or s == train_cfg.total_steps - 1:
                rec = {
                    "step": float(s),
                    "loss": loss,
                    "grad_norm": float(m.get("grad_norm", np.nan)),
                    "update_norm": float(m.get("update_norm", np.nan)),
                    "skipped": skipped,
                    **{k: float(v) for k, v in health.items()},
                    **monitor.counters(),
                }
                if refreshed and "refresh_overlap" in m:
                    # ||P_new^T P_old||_F^2 / r: 1 is a frozen subspace
                    rec["refresh_overlap"] = float(m["refresh_overlap"])
                if heartbeats is not None:
                    rec["stale_workers"] = float(len(heartbeats.stale()))
                if eval_fn is not None:
                    # a log step always flushes itself immediately, so the
                    # only log-step entry in the buffer is the current one
                    # -- eval_fn sees the same state as per-step fetching
                    rec.update(eval_fn(cur_state, s))
                history.append(rec)

    def _maybe_rebucket(cur_state: TrainState, s: int, group: int):
        """Schedule evaluation at a refresh boundary; on a rank change,
        the full re-bucket event: rebuild the optimizer at the new rank
        (fresh ``BucketPlan``/``StateLayout``), migrate live state through
        the canonical layout (``core.rank_schedule.migrate_opt_state`` --
        projectors truncated/zero-padded, moments sliced/zero-extended,
        quantized codes carried bit-exact), re-jit, rebind the checkpoint
        manager.  Runs AFTER the refresh step and metric flush and BEFORE
        the checkpoint save, so every checkpoint is written at the
        geometry its manifest meta declares."""
        rank_from, groups_from = lowrank_lib.current_ranks(optimizer)
        new_rank = None
        new_group_ranks = None
        if rank_sched.kind == "adaptive":
            eff = (
                spectrum.effective_rank_for(group)
                if spectrum is not None else None
            )
            if eff is None:
                return cur_state
            g = group % len(groups_from)
            prop = rank_schedule_lib.propose_adaptive_rank(
                rank_sched, groups_from[g], eff
            )
            if prop == groups_from[g]:
                return cur_state
            new_group_ranks = (
                groups_from[:g] + (prop,) + groups_from[g + 1:]
            )
        else:
            r = rank_schedule_lib.scheduled_rank(
                rank_sched, s,
                total_steps=train_cfg.total_steps, current=rank_from,
            )
            if r == rank_from:
                return cur_state
            new_rank = r
        old_opt = optimizer
        new_opt = lowrank_lib.rebuild_at_rank(
            old_opt, cur_state.params,
            rank=new_rank, group_ranks=new_group_ranks,
        )
        migrated = rank_schedule_lib.migrate_opt_state(
            old_opt, new_opt, cur_state.opt_state
        )
        _adopt_optimizer(new_opt)
        rank_to, _ = lowrank_lib.current_ranks(new_opt)
        history.append({
            "event": "rebucket",
            "step": float(s),
            "rank_from": float(rank_from),
            "rank_to": float(rank_to),
        })
        return TrainState(cur_state.params, migrated)

    step = start_step
    final_step = train_cfg.total_steps
    monitor.start_step()
    # the step of the most recent checkpoint KNOWN loadable (restored from
    # or pinned at start) -- reported on rollback exhaustion so the abort
    # message names where a manual restart can resume
    last_verified = start_step
    stale_action = (
        recovery.stale_worker_action if recovery is not None else "log"
    )
    try:
        while step < train_cfg.total_steps:
            with jax.profiler.StepTraceAnnotation("train", step_num=step):
                try:
                    if fault_plan is not None:
                        fault_plan.maybe_kill(step)  # injected process loss
                    with _span("repro.loop.data"):
                        batch = data.batch_at(step)
                        if batch_hook is not None:
                            batch = batch_hook(batch)
                        if fault_plan is not None:
                            batch = fault_plan.batch_hook(batch, step)
                    if heartbeats is not None:
                        heartbeats.beat(worker_name)
                        # staleness is evaluated EVERY step (not just at
                        # log_every cadence): each newly-stale worker is
                        # recorded with its first-stale step and escalated
                        # per the policy's stale_worker_action.
                        for w in heartbeats.check(step):
                            history.append({
                                "event": "stale_worker",
                                "worker": w,
                                "step": float(step),
                                "first_stale_step": float(
                                    heartbeats.first_stale[w]
                                ),
                                "action": stale_action,
                            })
                            if stale_action == "abort":
                                raise RuntimeError(
                                    f"worker {w!r} heartbeat stale at step "
                                    f"{step}; aborting per policy"
                                )
                            if stale_action == "rollback":
                                raise recovery_lib.RollbackNeeded(
                                    step, f"stale worker {w!r}"
                                )
                    if fault_plan is not None:
                        dt = fault_plan.sleep_s(step)
                        if dt > 0:
                            time.sleep(dt)  # straggler injection
                    # Staggered refresh: group g refreshes at steps where
                    # step % (tau/groups) == 0, cycling groups (DESIGN.md §2).
                    sub_tau = max(tau // groups, 1)
                    is_refresh = step % sub_tau == 0
                    if is_refresh:
                        group = (step // sub_tau) % groups
                        if spectrum is not None:
                            # host-snapshot the probe leaf BEFORE dispatch:
                            # the jitted step donates its input state
                            with _span("repro.loop.spectrum"):
                                spectrum.capture_before(state.params, group)
                        with _span("repro.loop.dispatch"):
                            state, m = step_fns["jit_refresh_step"](
                                state, batch, group=group
                            )
                    else:
                        with _span("repro.loop.dispatch"):
                            state, m = step_fns["jit_step"](state, batch)
                    if fault_plan is not None:
                        m = fault_plan.loss_hook(step, m)
                    pending.append((step, m, is_refresh))
                    if spectrum is not None and is_refresh:
                        with _span("repro.loop.spectrum"):
                            rec = spectrum.observe(state.params, step, group)
                        if rec is not None and getattr(
                            train_cfg, "log_spectrum", False
                        ):
                            history.append(rec)
                    if tracker is not None and is_refresh:
                        projs = metrics_lib.collect_projectors(
                            state.opt_state, optimizer.specs,
                            layout=optimizer.state_layout,
                        )
                        tracker.observe(
                            {k: np.asarray(v) for k, v in projs.items()}
                        )
                    if fault_plan is not None and fault_plan.preempt(step):
                        guard.requested = True  # as if SIGTERM were delivered
                    checkpoint_due = (
                        train_cfg.checkpoint_every > 0
                        and (step + 1) % train_cfg.checkpoint_every == 0
                    )
                    if (
                        is_refresh
                        or checkpoint_due
                        or guard.requested
                        or step % log_every == 0
                        or step == train_cfg.total_steps - 1
                    ):
                        _flush_metrics(state)
                    if rank_sched is not None and is_refresh:
                        with _span("repro.loop.rebucket"):
                            state = _maybe_rebucket(state, step, group)
                    if checkpoint_due:
                        _safe_save(
                            state, step + 1,
                            blocking=not train_cfg.async_checkpoint,
                        )
                    if guard.requested:
                        _safe_save(state, step + 1, blocking=True)
                        final_step = step + 1
                        break
                    step += 1
                except recovery_lib.RollbackNeeded as rb:
                    attempt = monitor.rollbacks + 1
                    if attempt > recovery.max_rollbacks:
                        raise FloatingPointError(
                            f"divergence persists after "
                            f"{recovery.max_rollbacks} rollbacks ({rb}); "
                            f"last verified step {last_verified}"
                        ) from rb
                    monitor.rollbacks = attempt
                    backoff = recovery.backoff_s(attempt)
                    if backoff > 0:
                        time.sleep(backoff)
                    _drain_save_error()  # never race an in-flight save
                    state, ck_step = _restore_latest(state)
                    last_verified = ck_step
                    monitor.start_step()  # a restore is no step's time
                    if recovery.resample_on_rollback:
                        # fold the attempt into the refresh RNG: stochastic
                        # selection (sara/golore/grass) draws a DIFFERENT
                        # subspace at the next refresh instead of replaying
                        # the diverged one (dominant re-selects the same
                        # subspace by construction -- see train/recovery.py)
                        state = TrainState(
                            state.params,
                            recovery_lib.resample_opt_state(
                                state.opt_state, attempt
                            ),
                        )
                    # truncate host-side records to the rollback point
                    if ck_step <= loss_base:
                        losses.clear()
                        loss_base = ck_step
                    else:
                        del losses[ck_step - loss_base:]
                    history[:] = [
                        r for r in history if r.get("step", -1.0) < ck_step
                    ]
                    pending.clear()
                    detector.reset()
                    monitor.bad_loss_count = 0
                    history.append({
                        "event": "rollback",
                        "step": float(ck_step),
                        "from_step": float(rb.step),
                        "attempt": float(attempt),
                        "reason": rb.reason,
                    })
                    step = ck_step
    finally:
        _flush_metrics(state, swallow_aborts=True)
        _drain_save_error()
        guard.restore()

    result = TrainResult(
        state=state, history=history, final_step=final_step, losses=losses
    )
    if tracker is not None:
        result.subspace = tracker  # type: ignore[attr-defined]
    return result
