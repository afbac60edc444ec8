"""Train/serve step builders: model + optimizer + mesh -> compiled callables.

Two training-step flavors:

* ``standard``  -- one ``jit`` over the global batch.  XLA SPMD inserts the
  DP gradient reduction implied by the param shardings (FSDP over ``data``,
  TP over ``model``, DP over ``pod``+``data``).

* ``compressed`` -- the beyond-paper *project-then-reduce* schedule: the step
  is a ``shard_map`` over the whole mesh whose specs split the batch over
  the DP axes only, so every other axis is replicated inside the region
  (see ``compressed_step_fn`` for why it is not partial-auto).  Per-shard
  gradients of low-rank
  leaves are projected to R-space BEFORE the cross-replica mean, shrinking
  DP gradient traffic by ~d/r on every non-refresh step (exact by
  linearity; P is replicated).  With a bucket-native optimizer the
  reduction payload is bucket-native too (DESIGN.md §2.7): ONE contiguous
  f32 (B, r, n) stack per bucket hot, one (B, d, n) full stack per bucket
  on refresh steps (which recompute projectors from the reduced stacks).
  In this mode params are NOT FSDP-sharded over the DP axes (they must be
  replica-identical inside the manual region); memory-for-bandwidth trade
  documented in EXPERIMENTS.md §Perf.

Both flavors call ``optimizer.update(..., apply=True)``: the optimizer
returns new params directly, so with ``engine="bucketed"`` the fused
kernels' W' output replaces the old separate ``apply_updates`` pass over
the params (one read + one write per param per step, donated buffers).

Both flavors build TWO executables -- (refresh=False) hot path and
(refresh=True) projector-refresh path -- selected by the caller on
``step % tau == 0``.  Keeping the SVD out of the hot executable keeps its HLO
clean (DESIGN.md §2).

Microbatching (gradient accumulation) wraps the loss-grad in a ``lax.scan``.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import TrainConfig
from repro.core import buckets as buckets_lib
from repro.core import lowrank as lowrank_lib
from repro.launch import sharding as shd
from repro.launch.mesh import axes_size, batch_axes
from repro.models.model_zoo import Model
from repro.train.state import TrainState

PyTree = Any


def _value_and_grad(model: Model, microbatch: int, accum_dtype=jnp.float32):
    """(params, batch) -> ((loss, metrics), grads), with optional accum.

    Accumulation sums per-microbatch gradients in ``accum_dtype``
    (``TrainConfig.accum_dtype``, f32 by default -- bf16 partial sums lose
    low-order bits across many microbatches) and returns them cast back to
    the parameter dtype, matching the non-accumulated path.  The global
    batch must divide evenly into microbatches: a silent floor-division
    reshape would drop the trailing samples.  ``microbatch >= batch`` is
    the lossless degenerate case (one microbatch, no accumulation) and
    stays allowed -- a production microbatch meeting a smoke-sized batch.
    """

    def single(params, batch):
        return jax.value_and_grad(model.loss, has_aux=True)(params, batch)

    if microbatch <= 0:
        return single

    acc_dt = jnp.dtype(accum_dtype)

    def accumulated(params, batch):
        gb = jax.tree_util.tree_leaves(batch)[0].shape[0]
        if microbatch >= gb:
            # a production microbatch meeting a smaller (smoke) batch:
            # one microbatch holds the whole batch -- unaccumulated,
            # lossless (the pre-fix clamp, kept on purpose).
            n_micro, mb_size = 1, gb
        elif gb % microbatch != 0:
            raise ValueError(
                f"global batch {gb} is not divisible by microbatch "
                f"{microbatch}: {gb % microbatch} trailing samples would "
                "be silently dropped -- pick a microbatch that divides "
                "the batch"
            )
        else:
            n_micro, mb_size = gb // microbatch, microbatch
        mb = jax.tree_util.tree_map(
            lambda x: x.reshape((n_micro, mb_size) + x.shape[1:]),
            batch,
        )

        def body(carry, micro):
            (loss_sum, grads_sum) = carry
            (loss, metrics), grads = single(params, micro)
            grads_sum = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(acc_dt), grads_sum, grads
            )
            return (loss_sum + loss, grads_sum), metrics

        zeros = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, acc_dt), params
        )
        # rolled scan: the point of accumulation is the activation-memory
        # saving; the dry-run corrects the while-body cost undercount with
        # an n_micro multiplier (launch/dryrun.py).
        (loss_sum, grads_sum), metrics = jax.lax.scan(
            body, (jnp.zeros(()), zeros), mb
        )
        grads = jax.tree_util.tree_map(
            lambda g, p: (g / n_micro).astype(p.dtype), grads_sum, params
        )
        last_metrics = jax.tree_util.tree_map(lambda m: m[-1], metrics)
        return (loss_sum / n_micro, last_metrics), grads

    return accumulated


def _split_grad_scale(batch):
    """Pop the fault-injection ``grad_scale`` scalar out of the batch.

    ``train/faults.py`` arms non-finite-gradient injection by adding a
    ``grad_scale`` entry to the batch dict (token batches are integer, so
    grads cannot be poisoned through the data); the step multiplies it
    into the gradients after the backward pass.  Returns (batch, scale) --
    scale is None on the (structurally distinct, separately compiled)
    fault-free batches, so ordinary runs pay nothing.
    """
    if isinstance(batch, dict) and "grad_scale" in batch:
        batch = dict(batch)
        return batch, batch.pop("grad_scale")
    return batch, None


def _scale_grads(grads, gscale):
    if gscale is None:
        return grads
    return jax.tree_util.tree_map(
        lambda g: g * jnp.asarray(gscale, g.dtype), grads
    )


def _largest_first(stacks):
    """Dispatch order for the per-bucket collectives: biggest payload
    first, so the longest-latency reduction is issued earliest and (under
    the latency-hiding scheduler, ``launch/runtime.py`` preset
    ``"overlap"``) has the most remaining compute to hide behind."""
    return sorted(range(len(stacks)), key=lambda i: (-stacks[i].size, i))


def _pmean_stacked(sg, dp):
    """Per-bucket DP mean of a ``StackedGrads``: one INDEPENDENT pmean per
    bucket stack, issued largest-first, plus one per full-rank leaf --
    instead of a single tuple psum over the whole structure.  Numerics are
    identical (psum is elementwise per operand); the win is schedule
    freedom: each collective carries its own dependency edge, so the async
    collective pass can start a bucket's reduction the moment that stack
    is ready rather than barriering every bucket at step end."""
    buckets = list(sg.buckets)
    for i in _largest_first(buckets):
        buckets[i] = jax.lax.pmean(buckets[i], dp)
    rest = tuple(jax.lax.pmean(r, dp) for r in sg.rest)
    return sg._replace(buckets=tuple(buckets), rest=rest)


def _reduce_scatter_stacked(sg, dp, nrep, layout):
    """ZeRO hot-path reduction: pad each bucket's R-space stack to the
    shardable batch, reduce-scatter its leading dim over the DP axes
    (largest-first), and mean the full-rank leaves.  Each replica ends up
    holding exactly the ``(B_pad/shards, r, n)`` slice its shard-local
    fused update consumes -- ~1/shards of the all-reduce bytes on the
    wire.  Dividing by a python-float replica count matches pmean's
    psum-then-divide bit-for-bit (pmean lowers to ``div(psum(x), n)``)."""
    padded = list(buckets_lib.zero_pad_grad_stacks(layout, sg.buckets))
    for i in _largest_first(padded):
        padded[i] = jax.lax.psum_scatter(
            padded[i], dp, scatter_dimension=0, tiled=True
        ) / nrep
    rest = tuple(jax.lax.pmean(r, dp) for r in sg.rest)
    return sg._replace(buckets=tuple(padded), rest=rest)


def make_train_step(
    model: Model,
    optimizer: lowrank_lib.LowRankOptimizer,
    *,
    mesh=None,
    train_cfg: Optional[TrainConfig] = None,
    compressed="",  # False/'' | True/'flat' | 'pod'
    donate: bool = True,
    recovery=None,  # Optional[repro.train.recovery.RecoveryPolicy]
    watchdog=None,  # Optional[repro.train.monitor.CollectiveWatchdog]
) -> Dict[str, Callable]:
    """Returns {'step': f(state, batch), 'refresh_step': f, 'jit_*': jitted}.

    The jitted versions carry in/out shardings when a mesh is given.

    ``compressed`` selects the project-then-reduce schedule: ``False``/''
    disables it, ``True`` is normalized to ``"flat"`` (all DP axes
    manual), ``"pod"`` compresses only the inter-pod axis.  Anything else
    raises immediately -- a typo like ``"pods"`` must not silently fall
    through to the flat-DP axis set.  The normalized mode is surfaced as
    ``fns["compressed_mode"]``.

    ``recovery`` with ``skip_nonfinite_updates=True`` compiles the
    skip-step gate into both executables (``optimizer.update(...,
    skip_nonfinite=True)``): non-finite gradients leave params and
    optimizer state untouched and surface as ``metrics["skipped"]``.

    Both flavors emit ``metrics["bad_step"]`` -- the coordinated recovery
    verdict (DESIGN.md §2.11).  In compressed mode it is ONE extra psum of
    a scalar over the DP axes (any shard's non-finite local loss, OR'd
    with the already-replica-identical skip flag), so every process reads
    the SAME verdict and the divergence detector's rollback decision is
    lockstep across the fleet by construction.  The standard jit flavor
    emits the local equivalent (XLA SPMD keeps it replica-identical).

    ``watchdog`` (a ``CollectiveWatchdog``) wraps the jitted steps with a
    bounded ``block_until_ready`` so a hung per-bucket collective is
    detected instead of stalling forever.  Opt-in: it forces a per-call
    device sync, trading the loop's deferred metric fetch for bounded
    detection latency.  Firings key on the jitted call ordinal.
    """
    # normalize the legacy bool form in ONE place, validate early
    compressed = "flat" if compressed is True else (compressed or "")
    if compressed not in ("", "flat", "pod"):
        raise ValueError(
            f"unknown compressed mode {compressed!r}: expected "
            "False/''/True/'flat'/'pod'"
        )
    if compressed and mesh is None:
        raise ValueError(
            f"compressed={compressed!r} needs a mesh (the project-then-"
            "reduce schedule is a shard_map over the DP axes)"
        )
    if compressed == "pod" and "pod" not in mesh.axis_names:
        raise ValueError(
            "'pod' compression needs a pod axis; mesh has "
            f"{mesh.axis_names}"
        )
    # ZeRO-sharded optimizer state (DESIGN.md §2.10): the shard count is
    # baked into the padded stacks at init, so it must equal the DP
    # replica count of the mesh the compressed step lowers on.
    zero = (optimizer.state_layout is not None
            and optimizer.state_layout.shards > 1)
    if zero and compressed:
        dp_axes = ("pod",) if compressed == "pod" else batch_axes(mesh)
        n = axes_size(mesh, dp_axes)
        if optimizer.config.state_shards != n:
            raise ValueError(
                f"state_sharding='zero' built with state_shards="
                f"{optimizer.config.state_shards}, but compressed="
                f"{compressed!r} lowers over DP axes {dp_axes} of total "
                f"size {n}; the shard count must equal the DP replica "
                "count"
            )
    # (the standard jit path is fine with any shard count: the update
    # unpads the replicated padded stacks at entry, so XLA SPMD handles
    # whatever placement shard_train_state chose)
    micro = train_cfg.microbatch if train_cfg else 0
    accum_dtype = getattr(train_cfg, "accum_dtype", jnp.float32) or jnp.float32
    vg = _value_and_grad(model, micro, accum_dtype)
    skip_nonfinite = bool(recovery is not None
                          and recovery.skip_nonfinite_updates)

    def step_fn(state: TrainState, batch, *, refresh: bool, group: int = 0):
        batch, gscale = _split_grad_scale(batch)
        (loss, metrics), grads = vg(state.params, batch)
        grads = _scale_grads(grads, gscale)
        # apply=True: the optimizer returns new params directly -- with
        # engine="bucketed" the fused kernels write W' themselves, so there
        # is no separate apply_updates pass over the parameters (and with
        # donation the param buffers are updated in place).
        params, opt_state, aux = optimizer.update(
            grads, state.opt_state, state.params, refresh=refresh,
            group=group, apply=True, skip_nonfinite=skip_nonfinite,
        )
        out_metrics = {
            **metrics,
            "grad_norm": aux.grad_norm,
            "update_norm": aux.update_norm,
            "refresh_overlap": aux.mean_refresh_overlap,
        }
        # single-jit flavor of the coordinated verdict: no collective
        # needed, XLA SPMD computes it replica-identically from the
        # already-reduced loss.
        bad = (~jnp.isfinite(loss)).astype(jnp.float32)
        if skip_nonfinite:
            out_metrics["skipped"] = aux.skipped
            bad = jnp.maximum(bad, aux.skipped)
        out_metrics["bad_step"] = bad
        return TrainState(params, opt_state), out_metrics

    def compressed_step_fn(
        state: TrainState, batch, *, refresh: bool, group: int = 0
    ):
        # 'pod' compression mode: only the slow INTER-POD axis carries the
        # compressed reduction -- gradients are projected to R-space before
        # crossing pods.  This is the hierarchical schedule the flat-
        # compressed experiments showed is needed at scale (EXPERIMENTS.md
        # §Perf cell 3).  Inside the (fully manual) region each pod's
        # (data, model) ranks compute the whole per-pod step redundantly.
        # the pod axis is validated at build time in make_train_step
        dp = ("pod",) if compressed == "pod" else batch_axes(mesh)
        if compressed == "pod":
            # dim0 splits across pods only.  0-dim entries (the
            # fault-injection grad_scale scalar) replicate.
            batch_specs = jax.tree_util.tree_map(
                lambda x: P("pod", *([None] * (x.ndim - 1)))
                if x.ndim and x.shape[0] % mesh.shape["pod"] == 0 else P(),
                batch,
            )
        else:
            batch_specs = jax.tree_util.tree_map(
                lambda x: shd.batch_spec(x.shape, mesh) if x.ndim else P(),
                batch,
            )

        # Bucket-native optimizers reduce in the stacked layout: ONE
        # contiguous buffer per bucket crosses the wire (plus the
        # full-rank leaves) instead of a ragged per-leaf tree -- fewer,
        # larger collectives for both 'flat' and 'pod' modes, each
        # dispatched as its own largest-first collective so the async
        # scheduler can overlap them with compute.  The reference engine
        # keeps the per-leaf project_grads path.
        stacked = optimizer.state_layout is not None
        # ZeRO mode on top of that: bucket stacks enter/leave the manual
        # region sharded over the DP axes (in/out specs below), the hot
        # reduction is a reduce-scatter, and the fused update runs on the
        # local rows only (core/lowrank.update(shard_axes=...)).
        shard_axes = dp if zero else None
        nrep = float(axes_size(mesh, dp))

        def shard_body(state, batch):
            batch, gscale = _split_grad_scale(batch)
            (loss, metrics), grads = vg(state.params, batch)
            grads = _scale_grads(grads, gscale)
            if refresh:
                if stacked:
                    # full-rank (B, d, n) stacks: same bytes as the leaf
                    # tree, one psum operand per bucket; the bucketed
                    # refresh engine consumes the reduced stacks directly.
                    # (ZeRO refresh keeps the full-stack reduction: the
                    # update gathers its state once, refreshes replicated,
                    # and re-slices -- amortized over tau hot steps.)
                    grads = _pmean_stacked(
                        lowrank_lib.stack_grads(optimizer, grads), dp
                    )
                else:
                    grads = jax.lax.pmean(grads, dp)
                params, opt_state, aux = optimizer.update(
                    grads, state.opt_state, state.params,
                    refresh=True, group=group, apply=True,
                    skip_nonfinite=skip_nonfinite, shard_axes=shard_axes,
                )
            else:
                if stacked:
                    # batched P^T G per bucket: f32 (B, r, n) stacks, ~d/r
                    # less DP traffic, straight from the projector buffers
                    # (ZeRO: the projector stacks are all-gathered inside
                    # project_grads_stacked -- every replica projects all
                    # B rows, then keeps only its slice of the reduction).
                    rgrads = lowrank_lib.project_grads_stacked(
                        optimizer, grads, state.opt_state,
                        shard_axes=shard_axes,
                    )
                    if zero:
                        rgrads = _reduce_scatter_stacked(
                            rgrads, dp, nrep, optimizer.state_layout
                        )
                    else:
                        rgrads = _pmean_stacked(rgrads, dp)
                else:
                    rgrads = jax.lax.pmean(
                        lowrank_lib.project_grads(
                            optimizer, grads, state.opt_state
                        ),
                        dp,
                    )
                # projected R-space grads feed the bucketed engine too: the
                # per-bucket projection stage is skipped, only the fused
                # moment+backproject+apply kernel runs.
                params, opt_state, aux = optimizer.update(
                    rgrads, state.opt_state, state.params,
                    refresh=False, projected=True, apply=True,
                    skip_nonfinite=skip_nonfinite, shard_axes=shard_axes,
                )
            metrics = jax.lax.pmean(metrics, dp)
            out_metrics = {
                **metrics,
                "grad_norm": aux.grad_norm,
                "update_norm": aux.update_norm,
                "refresh_overlap": aux.mean_refresh_overlap,
            }
            # Coordinated bad-step verdict: ONE scalar psum over the DP
            # axes of "my LOCAL (pre-reduction) loss went non-finite",
            # clamped to a flag -- every shard reads the same value, so
            # the host-side rollback decision is lockstep by construction
            # even when only one shard's data went bad.
            bad = jnp.minimum(
                jax.lax.psum(
                    (~jnp.isfinite(loss)).astype(jnp.float32), dp
                ),
                1.0,
            )
            if skip_nonfinite:
                # post-pmean stacks are replica-identical, so the gate (and
                # this flag) agree across the DP group -- in ZeRO mode the
                # update psums the per-shard verdict for the same reason.
                out_metrics["skipped"] = aux.skipped
                bad = jnp.maximum(bad, aux.skipped)
            out_metrics["bad_step"] = bad
            return TrainState(params, opt_state), out_metrics

        # ZeRO: bucket stacks are sharded over the DP axes on entry and
        # exit; everything else (params, rest-of-state, metrics) is
        # replicated exactly as before.
        state_specs = shd.zero_state_specs(state, dp) if zero else P()
        # Every mesh axis is manual; the specs name only the DP axes, so
        # the non-DP axes (``model``, and ``data`` in 'pod' mode) are
        # gathered at region entry and computed redundantly per rank.  A
        # partial-auto region (only ``dp`` manual) would keep TP inside,
        # but XLA's SPMD partitioner aborts on the embedding gather of an
        # auto-sharded table there (``PartitionGatherTrivialSliced...``),
        # and Pallas custom calls cannot be auto-partitioned either.
        return jax.shard_map(
            shard_body,
            mesh=mesh,
            in_specs=(state_specs, batch_specs),
            out_specs=(state_specs, P()),
            check_vma=False,
        )(state, batch)

    base = compressed_step_fn if compressed else step_fn

    fns = {
        "step": functools.partial(base, refresh=False),
        "refresh_step": functools.partial(base, refresh=True),
    }

    donate_args = (0,) if donate else ()
    fns["jit_step"] = jax.jit(fns["step"], donate_argnums=donate_args)
    refresh_groups = optimizer.config.refresh_groups
    fns["jit_refresh_step"] = jax.jit(
        functools.partial(base, refresh=True),
        static_argnames=("group",),
        donate_argnums=donate_args,
    )
    fns["refresh_groups"] = refresh_groups
    # Surfaced so launchers/benchmarks can report which hot path compiled
    # (and how many fused dispatches it takes per step).  ``state_layout``
    # is non-None when the optimizer state is bucket-native (stacked
    # moments/projectors donated straight into the fused kernels via
    # donate_argnums=(0,) on the TrainState).
    fns["engine"] = optimizer.config.engine
    fns["bucket_plan"] = optimizer.bucket_plan
    fns["state_layout"] = optimizer.state_layout
    # The normalized project-then-reduce mode ('' | 'flat' | 'pod') --
    # launchers/benchmarks report what actually compiled, not the raw
    # legacy-bool kwarg.
    fns["compressed_mode"] = compressed
    # '' (replicated) | 'zero' -- what the optimizer state layout carries;
    # launchers use it to pick zero placements in shard_train_state.
    fns["state_sharding"] = optimizer.config.state_sharding
    if watchdog is not None:
        def _guarded(fn):
            calls = [0]

            @functools.wraps(fn)
            def wrapped(*a, **k):
                out = fn(*a, **k)
                watchdog.guard(calls[0], out)
                calls[0] += 1
                return out

            return wrapped

        fns["jit_step"] = _guarded(fns["jit_step"])
        fns["jit_refresh_step"] = _guarded(fns["jit_refresh_step"])
    fns["watchdog"] = watchdog

    # Rank-elastic re-jit hook (DESIGN.md §2.12): rebuild this exact step
    # configuration around an optimizer re-bucketed at a new rank.  The
    # train loop calls it at a re-bucket event -- fresh executables for
    # the new bucket shapes (compressed-DP stack shapes follow the new
    # plan automatically); everything else (mesh, compression mode,
    # recovery, watchdog) carries over unchanged.
    def rebuild(new_optimizer: lowrank_lib.LowRankOptimizer):
        return make_train_step(
            model, new_optimizer, mesh=mesh, train_cfg=train_cfg,
            compressed=compressed, donate=donate, recovery=recovery,
            watchdog=watchdog,
        )

    fns["rebuild"] = rebuild
    return fns


def shard_train_state(
    state: TrainState, mesh, *, zero_dp_axes: Optional[Tuple[str, ...]] = None
) -> Tuple[TrainState, PyTree]:
    """Device-put a train state according to the sharding rules.

    ``zero_dp_axes``: for a ``state_sharding='zero'`` optimizer, the DP
    axes to partition each bucket stack's (padded) leading dim over --
    each device then physically holds only its 1/shards slice of the
    moments/codes/projectors (the ZeRO memory win outside the manual
    region too).  Default keeps the name-based rules (stacks replicated).
    """
    if zero_dp_axes:
        shardings = shd.zero_tree_shardings(state, mesh, zero_dp_axes)
    else:
        shardings = shd.tree_shardings(state, mesh)
    placed = jax.tree_util.tree_map(jax.device_put, state, shardings)
    return placed, shardings


# ---------------------------------------------------------------------------
# Serve steps
# ---------------------------------------------------------------------------


def make_prefill_fn(model: Model):
    def prefill_fn(params, batch):
        return model.prefill(params, batch)

    return prefill_fn


def make_decode_fn(model: Model):
    def decode_fn(params, cache, batch):
        return model.decode(params, cache, batch)

    return decode_fn
