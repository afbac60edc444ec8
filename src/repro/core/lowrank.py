"""The low-rank optimization wrapper (Algorithm 1) as a pure-JAX transform.

Composes a projector-selection method (``projectors.py``: dominant / SARA /
GoLore / Grass / online-PCA / identity) with an inner stateful optimizer
(``inner.py``: Adam / MSGD / Adafactor / Adam-mini / 8-bit Adam) over an
arbitrary parameter pytree, plus the Fira residual path.

Key departures from the reference torch implementation (all documented in
DESIGN.md §2):

  * The subspace refresh is **not** a ``lax.cond`` inside one step function.
    ``update(..., refresh=False)`` is the hot path (pure projected update);
    ``update(..., refresh=True)`` recomputes projectors.  The launcher JITs
    both and alternates on ``step % tau == 0``.  This keeps the hot step's
    HLO free of SVD branches (roofline cleanliness) and gives checkpointable,
    deterministic behavior.
  * Refresh can be **staggered**: leaves are statically partitioned into
    ``refresh_groups`` groups; calling ``update(refresh=True, group=g)``
    refreshes only group ``g``.  With ``refresh_groups=1`` (default) this is
    exactly the paper's all-layers-every-tau schedule.
  * Momentum carry across refreshes: ``keep`` (GaLore practice), ``reset``,
    or ``reproject`` (M' = P_new^T P_old M -- the momentum re-projection the
    convergence proof assumes; an r x r GEMM, negligible).
  * Stacked leaves (scan-over-layers (L, m, n), expert stacks (E, m, n))
    get vmapped projectors -- one batched SVD per stack instead of a python
    loop over layers.
  * The hot step has two executables of its own (DESIGN.md §2.3): the
    per-leaf einsum loop (``engine="reference"``, always available, covers
    Fira and every inner optimizer) and the **bucketed fused engine**
    (``engine="bucketed"``): low-rank leaves are statically grouped by
    canonical (d, n, rank, dtype) at build time and each bucket dispatches
    ONE batched fused kernel (kernels/lowrank_update) that projects,
    updates moments, back-projects, and writes W' in place of the separate
    ``apply_updates`` pass -- the full-space direction never reaches HBM.
    ``update(..., apply=True)`` returns new params directly; that is the
    mode ``train/step.py`` uses so param buffers are read/written once and
    can be donated.
  * With ``engine="bucketed"`` and a fused-eligible inner optimizer
    (adam, msgd, adam8bit, adam_mini -- adafactor's factored state stays
    on the reference path), the bucketed layout is also the **storage**
    layout (DESIGN.md §2.5, quantized layouts §2.8): moments and
    projectors live in per-bucket stacked ``(B, r, n)`` /
    ``(B, d, r)`` buffers (``LowRankOptState.buckets``) and the per-leaf
    ``LeafState`` entries of covered leaves are empty placeholders.  The
    hot step consumes/produces optimizer state with NO per-step
    stack/unstack; refresh scatters new projectors into the stacks and
    runs the ``momentum_carry="reproject"`` carry as one batched r x r
    einsum per bucket.  Checkpoints always serialize the canonical
    per-leaf layout: ``canonical_opt_state`` / ``storage_opt_state``
    convert losslessly in both directions, so resume and mid-run engine
    switching stay bit-for-bit.
  * The *refresh* executable is bucket-native too (DESIGN.md §2.6): with
    ``engine="bucketed"`` and a batchable projector config
    (``projectors.batched_refresh_supported`` -- SVD-free methods, or
    dominant/SARA on ``svd_backend="randomized"``), all same-group leaves
    of a bucket refresh as ONE batched randomized-subspace-iteration chain
    over their stacked (B, d, n) gradients (batched Gaussian sketch, fused
    ``kernels/power_iter`` power steps, batched thin QR, one small batched
    SVD, batched SARA Gumbel-top-k) instead of a per-leaf chain each.
    Per-slice RNG keys follow the exact per-leaf schedule (fold the global
    leaf index, split over leading dims), so batched and per-leaf refresh
    trajectories are bit-identical; ``svd_backend="exact"`` always falls
    back to the per-leaf loop, keeping paper-faithful runs untouched.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import buckets as buckets_lib
from repro.core import inner as inner_lib
from repro.core import projectors as proj_lib

PyTree = Any

# Leaves whose path matches any of these are always full-rank (GaLore
# convention: low-rank only on attention/MLP-style projection matrices).
DEFAULT_EXCLUDE = (
    "embed",
    "lm_head",
    "norm",
    "bias",
    "router",
    "gate_w",  # MoE router gate
    "conv",
    "a_log",
    "dt_",
    "scale",
    "pos_",
)


@dataclasses.dataclass(frozen=True)
class OptimizerConfig:
    """Everything needed to build Algorithm 1 (plus baselines)."""

    method: str = "sara"  # full|dominant|sara|golore|grass|online_pca|identity
    inner: str = "adam"
    rank: int = 128
    # Rank-elastic engine (DESIGN.md §2.12): a configs.base.RankSchedule
    # spec string ("cosine:128:32@0.5") declaring how rank moves over
    # training; "" keeps it static.  The schedule is evaluated HOST-SIDE
    # at refresh boundaries only (core/rank_schedule.py) -- a rank change
    # reshapes every bucket, so the train loop re-buckets (rebuild via
    # ``rebuild_at_rank``, migrate state, re-jit) rather than tracing it.
    rank_schedule: str = ""
    # Per-group rank overrides (adaptive schedules): when non-empty, leaf
    # rank = min(group_ranks[spec.group], d) instead of cfg.rank; length
    # must equal refresh_groups.  Produced by the adaptive policy -- the
    # global decay schedules leave it empty and move cfg.rank instead.
    group_ranks: Tuple[int, ...] = ()
    tau: int = 200
    alpha: float = 0.25  # GaLore scale factor applied to the low-rank update
    lr: float = 0.01
    lr_schedule: Optional[Callable[[jax.Array], jax.Array]] = None
    weight_decay: float = 0.0
    grad_clip_norm: float = 0.0  # 0 disables
    fira: bool = False
    fira_limiter: float = 1.0  # cap on the residual scaling ratio
    momentum_carry: str = "keep"  # keep | reset | reproject
    refresh_groups: int = 1
    # Hot-path update engine: "reference" (per-leaf einsum loop) or
    # "bucketed" (stacked fused kernels with bucket-native state storage
    # when the inner optimizer is fused-eligible: adam, msgd, and the
    # quantized adam8bit / adam_mini layouts of DESIGN.md §2.8; Fira and
    # adafactor fall back to the reference loop with per-leaf state, so
    # the flag is always safe to enable).
    engine: str = "reference"
    # Bucket-native batched refresh: with engine="bucketed" (+ bucket-native
    # state), all same-group entries of a bucket refresh as ONE batched
    # randomized-subspace-iteration chain over their stacked gradients
    # (core/buckets.bucketed_refresh + projectors.refresh_projector_stacked)
    # whenever projectors.batched_refresh_supported covers the config;
    # svd_backend="exact" always falls back to the per-leaf loop, so
    # paper-faithful runs are untouched.  False forces the per-leaf loop
    # everywhere (the two are bit-identical; this knob exists for A/B
    # benchmarks and bisection).
    batched_refresh: bool = True
    # aux.update_norm costs an extra W' - W read pass in apply mode; gate
    # it off for pure-throughput runs (benchmarks run with False).
    track_update_norm: bool = True
    # ZeRO-style optimizer-state sharding (DESIGN.md §2.10): "" keeps every
    # replica holding the full bucket stacks; "zero" pads each stack's
    # leading B dim to a multiple of state_shards (inert zero rows) so one
    # DP replica owns a contiguous row block of every buffer -- per-device
    # state drops by ~state_shards.  Requires bucket-native state (a fused
    # inner, no Fira).  state_shards must equal the DP replica count of the
    # mesh the train step runs on (train/step.py validates).
    state_sharding: str = ""  # "" | "zero"
    state_shards: int = 1
    min_dim: int = 16  # leaves with min(m,n) < this stay full-rank
    exclude: Tuple[str, ...] = DEFAULT_EXCLUDE
    seed: int = 0
    # projector knobs
    svd_backend: str = "exact"
    svd_oversample: int = 8
    svd_power_iters: int = 2
    sara_pool_factor: int = 4
    online_pca_lr: float = 0.1
    projector_dtype: Any = jnp.float32
    # inner-optimizer kwargs
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8

    def projector_config(self) -> proj_lib.ProjectorConfig:
        return proj_lib.ProjectorConfig(
            method=self.method,
            rank=self.rank,
            svd_backend=self.svd_backend,
            svd_oversample=self.svd_oversample,
            svd_power_iters=self.svd_power_iters,
            sara_pool_factor=self.sara_pool_factor,
            online_pca_lr=self.online_pca_lr,
            dtype=self.projector_dtype,
        )

    def inner_kwargs(self) -> Dict[str, Any]:
        """Inner-optimizer hyperparameters -- the ONE place the per-inner
        defaults live, shared by ``make_inner`` (reference path) and the
        fused bucketed engine (core/buckets.bucketed_update) so the two
        can never drift (e.g. adam_mini's b2 cap)."""
        if self.inner in ("adam", "adam8bit"):
            return dict(b1=self.b1, b2=self.b2, eps=self.eps)
        if self.inner == "msgd":
            return dict(b1=self.b1)
        if self.inner == "adam_mini":
            return dict(b1=self.b1, b2=min(self.b2, 0.95), eps=self.eps)
        if self.inner == "adafactor":
            return dict(b1=self.b1)
        return {}

    def make_inner(self) -> inner_lib.InnerOptimizer:
        return inner_lib.make_inner(self.inner, **self.inner_kwargs())


class LeafSpec(NamedTuple):
    """Static per-leaf plan (computed once at init from path + shape)."""

    path: str
    lowrank: bool
    side: str  # 'left' | 'right' (ignored if not lowrank)
    rank: int
    group: int  # refresh group


class LeafState(NamedTuple):
    projector: jax.Array  # (.., d, r) or () placeholder for full-rank leaves
    inner: Any


class LowRankOptState(NamedTuple):
    step: jax.Array  # int32 scalar, number of updates applied so far
    key: jax.Array  # PRNG key for sampling-based refreshes
    leaves: PyTree  # pytree of LeafState, same treedef as params
    # Storage-layout bucket stacks (tuple of buckets_lib.BucketState) when
    # the optimizer is bucket-native; () for the canonical per-leaf layout
    # (reference engine, non-fused inners, Fira, and every checkpoint).
    buckets: Any = ()


class StackedGrads(NamedTuple):
    """Bucket-native gradient layout for the distributed path.

    ``buckets`` holds one contiguous stack per bucket of the optimizer's
    ``BucketPlan`` (in plan order): f32 ``(B, r, n)`` R-space stacks on
    the hot project-then-reduce path, or full ``(B, d, n)`` stacks
    (canonical orientation) on refresh steps.  ``rest`` holds the
    gradients of every NON-bucketed leaf, in ascending leaf-index order
    (the indices are static -- ``LowRankOptimizer`` recovers them from its
    plan).  The whole structure is a pytree of dense arrays, so
    ``jax.lax.pmean`` over it dispatches exactly ``len(buckets) +
    len(rest)`` reduction operands -- the fewer, larger collectives the
    compressed-DP schedule exists for.
    """

    buckets: Tuple[jax.Array, ...]
    rest: Tuple[jax.Array, ...]


class AuxInfo(NamedTuple):
    """Diagnostics returned by update (all scalars / small)."""

    grad_norm: jax.Array
    update_norm: jax.Array
    mean_refresh_overlap: jax.Array  # overlap(P_new, P_old) avg over refreshed
    # 1.0 when skip_nonfinite gated the update out (non-finite grads seen),
    # 0.0 otherwise (always 0.0 with the gate disabled)
    skipped: Any = None


def _path_str(path) -> str:
    return jax.tree_util.keystr(path)


def default_lowrank_filter(
    path: str, shape: Tuple[int, ...], cfg: OptimizerConfig
) -> bool:
    if cfg.method == "full":
        return False
    if len(shape) < 2:
        return False
    if min(shape[-2], shape[-1]) < cfg.min_dim:
        return False
    low = path.lower()
    return not any(pat in low for pat in cfg.exclude)


def build_specs(
    params: PyTree,
    cfg: OptimizerConfig,
    lowrank_filter: Optional[Callable[[str, Tuple[int, ...]], bool]] = None,
) -> PyTree:
    """Static plan: one LeafSpec per param leaf."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    specs = []
    n_lowrank = 0
    for path, leaf in flat:
        ps = _path_str(path)
        if lowrank_filter is not None:
            lowrank = lowrank_filter(ps, leaf.shape)
        else:
            lowrank = default_lowrank_filter(ps, leaf.shape, cfg)
        if lowrank:
            side = proj_lib.projection_side(leaf.shape)
            group = n_lowrank % max(cfg.refresh_groups, 1)
            base_rank = (
                cfg.group_ranks[group] if cfg.group_ranks else cfg.rank
            )
            rank = min(base_rank, proj_lib.projector_dim(leaf.shape))
            n_lowrank += 1
        else:
            side, rank, group = "left", 0, 0
        specs.append(LeafSpec(ps, lowrank, side, rank, group))
    return jax.tree_util.tree_unflatten(treedef, specs)


def _projector_shape(shape: Tuple[int, ...], side: str, rank: int):
    batch = shape[:-2]
    d = min(shape[-2], shape[-1])
    return batch + (d, rank)


class LowRankOptimizer(NamedTuple):
    """(init, update, specs).  update's ``refresh``/``group``/``apply`` are
    static.  ``bucket_plan`` is the static bucketing of low-rank leaves the
    ``engine="bucketed"`` hot path dispatches over (None for full-rank);
    ``state_layout`` is non-None iff the optimizer state is stored
    bucket-native (stacked moments/projectors in ``state.buckets``)."""

    init: Callable[[PyTree], LowRankOptState]
    update: Callable[..., Tuple[PyTree, LowRankOptState, AuxInfo]]
    specs: PyTree
    config: OptimizerConfig
    bucket_plan: Optional[buckets_lib.BucketPlan] = None
    state_layout: Optional[buckets_lib.StateLayout] = None


def _placeholder_leaf() -> LeafState:
    """Empty per-leaf slot for a leaf whose state lives in bucket stacks."""
    return LeafState(projector=jnp.zeros((), jnp.float32), inner=None)


def _global_norm(tree: PyTree) -> jax.Array:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in leaves)
    )


def make_lowrank_optimizer(
    cfg: OptimizerConfig,
    params_like: PyTree,
    lowrank_filter: Optional[Callable[[str, Tuple[int, ...]], bool]] = None,
) -> LowRankOptimizer:
    """Build the optimizer for a concrete parameter structure."""
    if cfg.method not in ("full",) + proj_lib.METHODS:
        raise ValueError(f"unknown method {cfg.method!r}")
    if cfg.momentum_carry not in ("keep", "reset", "reproject"):
        raise ValueError(f"unknown momentum_carry {cfg.momentum_carry!r}")
    if cfg.engine not in ("reference", "bucketed"):
        raise ValueError(f"unknown engine {cfg.engine!r}")
    if cfg.state_sharding not in ("", "zero"):
        raise ValueError(f"unknown state_sharding {cfg.state_sharding!r}")
    if cfg.state_sharding == "zero" and cfg.state_shards < 1:
        raise ValueError(f"state_shards must be >= 1, got {cfg.state_shards}")
    if cfg.rank < 1:
        raise ValueError(f"rank must be >= 1, got {cfg.rank}")
    if cfg.group_ranks:
        if len(cfg.group_ranks) != max(cfg.refresh_groups, 1):
            raise ValueError(
                f"group_ranks has {len(cfg.group_ranks)} entries for "
                f"{max(cfg.refresh_groups, 1)} refresh groups"
            )
        if any(r < 1 for r in cfg.group_ranks):
            raise ValueError(f"group_ranks must all be >= 1: {cfg.group_ranks}")
    if cfg.rank_schedule:
        # Fail at build time, not at the first refresh boundary: the
        # schedule itself is evaluated by the train loop / dryrun
        # (core/rank_schedule.py); here we only validate the spec parses.
        from repro.configs.base import RankSchedule

        RankSchedule.parse(cfg.rank_schedule)
    specs = build_specs(params_like, cfg, lowrank_filter)
    inner = cfg.make_inner()
    pcfg = cfg.projector_config()

    is_spec = lambda x: isinstance(x, LeafSpec)  # noqa: E731
    flat_specs_static, spec_treedef = jax.tree_util.tree_flatten(
        specs, is_leaf=is_spec
    )
    bucket_plan: Optional[buckets_lib.BucketPlan] = None
    state_layout: Optional[buckets_lib.StateLayout] = None
    if cfg.engine == "bucketed":
        bucket_plan = buckets_lib.build_bucket_plan(
            flat_specs_static, spec_treedef.flatten_up_to(params_like),
            # quantized inners need side-homogeneous buckets: adam_mini's
            # per-row v and adam8bit's scales follow the per-leaf rows,
            # which transpose with the slices (DESIGN.md §2.8)
            split_sides=cfg.inner in buckets_lib.SIDE_HOMOGENEOUS_INNERS,
        )
        # Bucket-native storage: when the fused engine covers EVERY hot
        # step of EVERY low-rank leaf (fused inner: adam / msgd /
        # adam8bit / adam_mini, no Fira), moments and projectors live
        # stacked.  Otherwise (adafactor / Fira fall through to the
        # reference loop) state stays per-leaf and the plan is used for
        # accounting only.
        if bucket_plan.buckets and inner.fused_eligible and not cfg.fira:
            state_layout = buckets_lib.build_state_layout(
                bucket_plan, flat_specs_static,
                spec_treedef.flatten_up_to(params_like),
                inner_name=cfg.inner, projector_dtype=cfg.projector_dtype,
                shards=(cfg.state_shards
                        if cfg.state_sharding == "zero" else 1),
            )
    if cfg.state_sharding == "zero" and state_layout is None:
        raise ValueError(
            "state_sharding='zero' shards the bucket stacks, so it needs "
            "bucket-native state: engine='bucketed' with a fused inner "
            "(adam/msgd/adam8bit/adam_mini), no Fira, and at least one "
            "bucketed leaf"
        )
    # Static leaf indices NOT covered by any bucket -- the ``rest`` order
    # of ``StackedGrads`` (full-rank leaves; with a bucket-native layout
    # every low-rank leaf is bucketed).
    rest_indices: Tuple[int, ...] = tuple(
        i for i in range(len(flat_specs_static))
        if bucket_plan is None or i not in bucket_plan.bucketed
    )

    def init(params: PyTree) -> LowRankOptState:
        def leaf_init(spec: LeafSpec, p: jax.Array) -> LeafState:
            if spec.lowrank:
                if state_layout is not None:
                    # bucket-native: this leaf's projector and moments
                    # live in the bucket stacks; keep an empty slot.
                    return _placeholder_leaf()
                pshape = _projector_shape(p.shape, spec.side, spec.rank)
                # Deterministic init: dominant-like placeholder (eye) --
                # the first refresh (step 0) installs the real projector
                # before any update consumes it.
                d, r = pshape[-2], pshape[-1]
                eye = jnp.eye(d, r, dtype=cfg.projector_dtype)
                proj = jnp.broadcast_to(eye, pshape)
                if spec.side == "left":
                    rshape = p.shape[:-2] + (spec.rank, p.shape[-1])
                else:
                    rshape = p.shape[:-2] + (p.shape[-2], spec.rank)
                inner_state = inner.init(jnp.zeros(rshape, jnp.float32))
                return LeafState(projector=proj, inner=inner_state)
            return LeafState(
                projector=jnp.zeros((), jnp.float32),
                inner=inner.init(p),
            )

        leaves = jax.tree_util.tree_map(
            leaf_init, specs, params,
            is_leaf=lambda x: isinstance(x, LeafSpec),
        )
        bucket_states = (
            buckets_lib.init_bucket_states(state_layout)
            if state_layout is not None else ()
        )
        return LowRankOptState(
            step=jnp.zeros((), jnp.int32),
            key=jax.random.PRNGKey(cfg.seed),
            leaves=leaves,
            buckets=bucket_states,
        )

    def _lr_at(step: jax.Array) -> jax.Array:
        if cfg.lr_schedule is not None:
            return jnp.asarray(cfg.lr_schedule(step), jnp.float32)
        return jnp.asarray(cfg.lr, jnp.float32)

    def _refresh_leaf(
        spec: LeafSpec,
        st: LeafState,
        g: jax.Array,
        key: jax.Array,
    ) -> Tuple[LeafState, jax.Array]:
        """New projector + momentum carry.  Returns (state, overlap)."""
        old_p = st.projector
        new_p = proj_lib.refresh_projector(
            g, key, old_p, pcfg, side=spec.side, rank=spec.rank
        )
        r = spec.rank
        # C[new, old] = P_new^T P_old; also the overlap diagnostic (GARD18):
        # overlap = ||P_new^T P_old||_F^2 / r.
        c = jnp.einsum("...dn,...do->...no", new_p, old_p)
        overlap = jnp.mean(jnp.sum(c.astype(jnp.float32) ** 2, axis=(-2, -1)) / r)
        inner_state = st.inner
        if cfg.momentum_carry == "reset":
            inner_state = jax.tree_util.tree_map(jnp.zeros_like, inner_state)
        elif cfg.momentum_carry == "reproject":
            # Re-express the first moment in the new basis (the momentum
            # re-projection the convergence proof assumes).  Left side:
            # M' = C M  (r x r GEMM); right side: M' = M C^T.  The second
            # moment is elementwise and not linearly transformable -- kept
            # as-is (documented).
            if hasattr(inner_state, "m"):
                m = inner_state.m
                if spec.side == "left":
                    # M (old_r, n) -> (new_r, n)
                    m2 = jnp.einsum("...no,...ok->...nk", c, m)
                else:
                    # M (m, old_r) -> (m, new_r), as (C M^T)^T: the
                    # canonical operand order (see projectors.project)
                    m2 = jnp.swapaxes(jnp.einsum(
                        "...no,...ok->...nk", c, jnp.swapaxes(m, -1, -2)
                    ), -1, -2)
                inner_state = inner_state._replace(m=m2.astype(m.dtype))
        return LeafState(projector=new_p, inner=inner_state), overlap

    # the hot path's ops carry the scope; the refresh's nest "opt_refresh"
    @jax.named_scope("opt_update")
    def update(
        grads: PyTree,
        state: LowRankOptState,
        params: PyTree,
        *,
        refresh: bool,
        group: int = 0,
        projected: bool = False,
        apply: bool = False,
        skip_nonfinite: bool = False,
        shard_axes: Optional[Tuple[str, ...]] = None,
    ) -> Tuple[PyTree, LowRankOptState, AuxInfo]:
        """Returns (updates, new_state, aux); apply via params + updates.

        ``shard_axes`` (zero-sharded optimizers only, inside shard_map):
        the mesh axis names the bucket state is sharded over.  Hot steps
        then consume SHARD-LOCAL row blocks -- ``state.buckets`` hold the
        local slices and ``grads.buckets`` the reduce-scattered local
        R-space slices -- run the fused kernels on ``B_pad/shards`` rows,
        and all-gather only the updated W' row slices back to full
        parameters.  Refresh steps all-gather the state once and run the
        replicated batched refresh bit-identically (amortized over
        ``tau``).  The skip-step gate psums ONE scalar verdict across
        shards so every replica skips (or applies) in lockstep -- a shard
        whose local rows are clean must not apply while another skips.
        Without ``shard_axes`` a zero-sharded optimizer computes on the
        full padded stacks (the replicated representation every
        single-process path sees).

        ``skip_nonfinite=True`` (the recovery skip-step gate, DESIGN.md
        §2.9): compute ONE fused all-finite reduction per bucket gradient
        stack (plus a cheap per-leaf check over the few non-bucketed
        leaves) and ``jnp.where``-gate the whole update on it -- with any
        non-finite gradient the params AND optimizer state pass through
        unchanged (``aux.skipped = 1.0``) instead of poisoning the moments.
        When every gradient is finite the gate selects the new values
        exactly -- it adds no numerical perturbation of its own (across a
        recompile XLA may still fuse differently, so gated vs. ungated
        *programs* agree only to rounding).

        ``projected=True``: low-rank leaves of ``grads`` already hold the
        R-space gradient (P^T G / G P) -- the distributed project-then-reduce
        path computes and psums them *before* calling update, cutting DP
        traffic by ~d/r.  Incompatible with refresh (SVD needs full G) and
        with Fira (the residual needs full G).

        ``grads`` may also be a ``StackedGrads`` (bucket-native optimizers
        only): per-bucket ``(B, r, n)`` R-space stacks with
        ``projected=True`` (the hot project-then-reduce payload,
        ``project_grads_stacked``), or per-bucket full ``(B, d, n)``
        stacks with ``refresh=True`` (``stack_grads``).  Either way the
        stacks feed the fused engine directly -- compressed gradients
        never round-trip through per-leaf layout.

        ``apply=True``: return NEW PARAMS instead of updates -- the fused
        kernels of the bucketed engine emit W' directly, so no full-space
        update pytree is ever materialized and the separate
        ``apply_updates`` pass disappears (params read/written once).  The
        reference engine honors the same contract by applying internally.
        """
        if projected and refresh:
            raise ValueError("projected gradients cannot drive a refresh step")
        if projected and cfg.fira:
            raise ValueError("Fira needs full-rank grads (residual term)")
        stacked_in = isinstance(grads, StackedGrads)
        if stacked_in:
            if state_layout is None:
                raise ValueError(
                    "StackedGrads need a bucket-native optimizer "
                    "(engine='bucketed' with a fused inner, no Fira)"
                )
            if not (projected or refresh):
                raise ValueError(
                    "StackedGrads hold R-space stacks (projected=True) or "
                    "full-rank refresh stacks (refresh=True); a plain hot "
                    "step takes the per-leaf gradient tree"
                )
            if (len(grads.buckets) != len(bucket_plan.buckets)
                    or len(grads.rest) != len(rest_indices)):
                raise ValueError(
                    "StackedGrads shape mismatch: expected "
                    f"{len(bucket_plan.buckets)} bucket stacks + "
                    f"{len(rest_indices)} rest leaves, got "
                    f"{len(grads.buckets)} + {len(grads.rest)}"
                )
        zero_layout = state_layout is not None and state_layout.shards > 1
        shard_local = zero_layout and shard_axes is not None
        if shard_axes is not None and not zero_layout:
            raise ValueError(
                "shard_axes is only meaningful for a zero-sharded "
                "optimizer (state_sharding='zero', state_shards > 1)"
            )
        if shard_local and not stacked_in:
            raise ValueError(
                "shard-local updates take StackedGrads (the reduce-"
                "scattered hot payload or full refresh stacks)"
            )
        shard_index = None
        if zero_layout and not shard_local:
            # replicated representation: compute on the unpadded stacks,
            # repad at exit (pad rows stay zero by construction).
            state = state._replace(buckets=buckets_lib.zero_unpad_states(
                state_layout, state.buckets
            ))
        if shard_local:
            shard_index = buckets_lib.zero_shard_index(shard_axes)
            if refresh:
                # gather-once refresh: reassemble the full padded stacks,
                # unpad, and fall through to the replicated batched
                # refresh + update (bit-identical to the unsharded
                # schedule); the result is re-sliced local at exit.
                full = buckets_lib.zero_gather_states(
                    state.buckets, shard_axes
                )
                state = state._replace(
                    buckets=buckets_lib.zero_unpad_states(state_layout, full)
                )
        step = state.step + 1  # 1-indexed for bias correction
        lr = _lr_at(state.step)

        finite_ok = None
        if skip_nonfinite:
            # pre-clip grads: a NaN gnorm makes the clip scale poison every
            # leaf, so check the raw stacks (one fused reduction per bucket
            # -- bucketed_all_finite; XLA CSEs the gathers against the
            # update's own)
            if stacked_in:
                checks = list(buckets_lib.bucketed_all_finite(
                    bucket_plan, stacked_grads=grads.buckets
                ))
                checks += [jnp.all(jnp.isfinite(g)) for g in grads.rest]
            elif bucket_plan is not None and bucket_plan.buckets:
                flat_g = spec_treedef.flatten_up_to(grads)
                checks = list(buckets_lib.bucketed_all_finite(
                    bucket_plan, flat_g
                ))
                checks += [
                    jnp.all(jnp.isfinite(flat_g[i])) for i in rest_indices
                ]
            else:
                checks = [
                    jnp.all(jnp.isfinite(g))
                    for g in jax.tree_util.tree_leaves(grads)
                ]
            finite_ok = checks[0] if checks else jnp.asarray(True)
            for c in checks[1:]:
                finite_ok = jnp.logical_and(finite_ok, c)
            if shard_local:
                # ONE fused scalar psum of the verdict: local checks only
                # cover this shard's rows of the scattered stacks, and all
                # shards must agree on skip-vs-apply or state diverges.
                bad = jax.lax.psum(
                    1.0 - finite_ok.astype(jnp.float32), tuple(shard_axes)
                )
                finite_ok = bad == 0.0

        if shard_local and not refresh:
            # grads.buckets are disjoint local row blocks: the global norm
            # is psum(local sq) + the replicated rest (pad rows are zero).
            bsq = sum(
                jnp.sum(jnp.square(x.astype(jnp.float32)))
                for x in grads.buckets
            )
            bsq = jax.lax.psum(bsq, tuple(shard_axes))
            rsq = sum(
                jnp.sum(jnp.square(g.astype(jnp.float32)))
                for g in grads.rest
            )
            gnorm = jnp.sqrt(bsq + rsq)
        else:
            gnorm = _global_norm(grads)
        if cfg.grad_clip_norm and cfg.grad_clip_norm > 0:
            scale = jnp.minimum(1.0, cfg.grad_clip_norm / (gnorm + 1e-12))
            grads = jax.tree_util.tree_map(lambda g: g * scale, grads)

        key = state.key
        if refresh:
            key, subkey = jax.random.split(key)
        else:
            subkey = key  # unused

        flat_specs = flat_specs_static
        flat_states = spec_treedef.flatten_up_to(state.leaves)
        if stacked_in:
            # bucketed leaves live in ``grads.buckets``; their per-leaf
            # slots stay None (the fused engine never reads them).
            flat_grads = [None] * len(flat_specs)
            for j, i in enumerate(rest_indices):
                flat_grads[i] = grads.rest[j]
            stacked_grads = grads.buckets
        else:
            flat_grads = spec_treedef.flatten_up_to(grads)
            stacked_grads = None
        flat_params = spec_treedef.flatten_up_to(params)

        overlaps = []

        # Bucket-native path: the stacks in ``state.buckets`` ARE the
        # moments/projectors, so the fused kernels consume and produce
        # them directly -- no per-step gather/scatter of optimizer state.
        # Refresh steps scatter new projectors into the stacks (and carry
        # momentum with one batched r x r einsum per bucket), then run the
        # same fused update with the fresh projectors, exactly like the
        # reference loop's refresh-then-update order.
        fused: dict = {}
        new_bucket_states = state.buckets
        bucket_norm_sq: list = []
        if state_layout is not None:
            if not state.buckets:
                raise ValueError(
                    "bucket-native optimizer got a canonical per-leaf "
                    "state; convert with storage_opt_state(optimizer, state)"
                )
            if refresh:
                def _refresh_fn(g, lkey, old_p, spec):
                    return proj_lib.refresh_projector(
                        g, lkey, old_p, pcfg, side=spec.side, rank=spec.rank
                    )

                _stacked_fn = None
                if cfg.batched_refresh and proj_lib.batched_refresh_supported(
                    pcfg
                ):
                    def _stacked_fn(gs, keys, old_ps, rank):
                        return proj_lib.refresh_projector_stacked(
                            gs, keys, old_ps, pcfg, rank=rank
                        )

                with jax.named_scope("opt_refresh"):
                    new_bucket_states, bucket_overlaps = (
                        buckets_lib.bucketed_refresh(
                            state_layout, state.buckets, flat_specs,
                            flat_grads, subkey, _refresh_fn,
                            group=group % max(cfg.refresh_groups, 1),
                            momentum_carry=cfg.momentum_carry,
                            stacked_refresh_fn=_stacked_fn,
                            stacked_grads=stacked_grads,
                        )
                    )
                overlaps.extend(bucket_overlaps)
            if shard_local and not refresh:
                # ZeRO hot step: slice this shard's W rows, run the fused
                # kernels on local row blocks only, then all-gather just
                # the updated W' slices (the only full-copy the step
                # needs) and scatter them back to the parameter leaves.
                local_w = buckets_lib.zero_local_param_stacks(
                    state_layout, flat_params, shard_index
                )
                out_stacks, new_bucket_states, bucket_norm_sq = (
                    buckets_lib.bucketed_update(
                        bucket_plan, cfg, new_bucket_states, flat_grads,
                        flat_params, step, lr, projected=projected,
                        apply=apply, track_norm=cfg.track_update_norm,
                        stacked_grads=stacked_grads,
                        stacked_params=local_w, out_stacked=True,
                    )
                )
                full_stacks = buckets_lib.zero_gather_stacks(
                    state_layout, out_stacks, shard_axes
                )
                fused = buckets_lib.zero_scatter_outputs(
                    bucket_plan, full_stacks, flat_params
                )
            else:
                fused, new_bucket_states, bucket_norm_sq = (
                    buckets_lib.bucketed_update(
                        bucket_plan, cfg, new_bucket_states, flat_grads,
                        flat_params, step, lr, projected=projected,
                        apply=apply, track_norm=cfg.track_update_norm,
                        stacked_grads=stacked_grads,
                    )
                )

        flat_out = []  # updates, or new params for fused leaves when apply
        flat_norm_sq = []  # per-leaf squared update norms (aux)
        flat_new_states = []

        def _norm_sq(u):
            return jnp.sum(jnp.square(u.astype(jnp.float32)))

        for i, (spec, st, g, p) in enumerate(
            zip(flat_specs, flat_states, flat_grads, flat_params)
        ):
            if i in fused:
                # norm already accounted stacked (bucket_norm_sq); the
                # per-leaf slot is a placeholder and stays as-is.
                flat_out.append(fused[i])
                flat_new_states.append(st)
                continue

            if not spec.lowrank:
                direction, inner_state = inner.update(g, st.inner, step)
                upd = -lr * direction
                if cfg.weight_decay:
                    upd = upd - lr * cfg.weight_decay * p.astype(jnp.float32)
                upd = upd.astype(p.dtype)
                if cfg.track_update_norm:
                    flat_norm_sq.append(_norm_sq(upd))
                flat_out.append((p + upd) if apply else upd)
                flat_new_states.append(
                    LeafState(projector=st.projector, inner=inner_state)
                )
                continue

            if refresh and spec.group == (group % max(cfg.refresh_groups, 1)):
                lkey = jax.random.fold_in(subkey, i)
                with jax.named_scope("opt_refresh"):
                    st, ov = _refresh_leaf(spec, st, g, lkey)
                overlaps.append(ov)

            proj = st.projector
            r_g = g if projected else proj_lib.project(g, proj, spec.side)
            direction, inner_state = inner.update(r_g, st.inner, step)
            full_dir = proj_lib.backproject(
                direction.astype(proj.dtype), proj, spec.side
            )
            upd = -lr * cfg.alpha * full_dir.astype(jnp.float32)
            if cfg.fira:
                # Fira: add the projection residual, scaled by the ratio of
                # the adapted-update norm to the raw projected-grad norm,
                # capped by the limiter (spike protection).
                s_res = g.astype(jnp.float32) - proj_lib.backproject(
                    r_g, proj, spec.side
                ).astype(jnp.float32)
                ratio = _safe_ratio(direction, r_g)
                ratio = jnp.minimum(ratio, cfg.fira_limiter)
                upd = upd - lr * cfg.alpha * ratio * s_res
            if cfg.weight_decay:
                upd = upd - lr * cfg.weight_decay * p.astype(jnp.float32)
            upd = upd.astype(p.dtype)
            if cfg.track_update_norm:
                flat_norm_sq.append(_norm_sq(upd))
            flat_out.append((p + upd) if apply else upd)
            flat_new_states.append(
                LeafState(projector=st.projector, inner=inner_state)
            )

        out_tree = jax.tree_util.tree_unflatten(spec_treedef, flat_out)
        new_leaves = jax.tree_util.tree_unflatten(spec_treedef, flat_new_states)

        if cfg.track_update_norm:
            bucket_sq = sum(bucket_norm_sq)
            if shard_local and not refresh:
                # local row blocks are disjoint -- one scalar psum
                bucket_sq = jax.lax.psum(bucket_sq, tuple(shard_axes))
            unorm = jnp.sqrt(sum(flat_norm_sq) + bucket_sq)
        else:
            unorm = jnp.zeros(())
        mean_overlap = (
            jnp.mean(jnp.stack(overlaps)) if overlaps else jnp.zeros(())
        )
        new_state = LowRankOptState(
            step=step, key=key, leaves=new_leaves, buckets=new_bucket_states
        )
        skipped = jnp.zeros(())
        if skip_nonfinite:
            # Gate the WHOLE transition on the finite check: params (or
            # updates) and every piece of optimizer state -- step, refresh
            # key, moments, projectors -- fall back to their old values on
            # a bad step.  jnp.where(True, new, old) IS new: the gate
            # itself never perturbs a fault-free run.
            ok = finite_ok

            def _keep(new, old):
                return jnp.where(ok, new, old)

            if apply:
                out_tree = jax.tree_util.tree_map(_keep, out_tree, params)
            else:
                out_tree = jax.tree_util.tree_map(
                    lambda u: jnp.where(ok, u, jnp.zeros_like(u)), out_tree
                )
            new_state = jax.tree_util.tree_map(_keep, new_state, state)
            skipped = 1.0 - ok.astype(jnp.float32)
        if zero_layout:
            # Restore the zero-sharded representation (gating above ran on
            # the layout `state` itself used, so shapes always matched):
            # replicated callers get the padded full stacks back, a
            # shard-local refresh re-slices its local rows out of the full
            # result; shard-local hot steps already hold local rows.
            if not shard_local:
                new_state = new_state._replace(
                    buckets=buckets_lib.zero_pad_states(
                        state_layout, new_state.buckets
                    )
                )
            elif refresh:
                new_state = new_state._replace(
                    buckets=buckets_lib.zero_local_states(
                        state_layout,
                        buckets_lib.zero_pad_states(
                            state_layout, new_state.buckets
                        ),
                        shard_index,
                    )
                )
        aux = AuxInfo(
            grad_norm=gnorm, update_norm=unorm,
            mean_refresh_overlap=mean_overlap, skipped=skipped,
        )
        return out_tree, new_state, aux

    return LowRankOptimizer(
        init=init, update=update, specs=specs, config=cfg,
        bucket_plan=bucket_plan, state_layout=state_layout,
    )


def rebuild_at_rank(
    optimizer: "LowRankOptimizer",
    params_like: PyTree,
    *,
    rank: Optional[int] = None,
    group_ranks: Optional[Tuple[int, ...]] = None,
    lowrank_filter: Optional[Callable] = None,
) -> "LowRankOptimizer":
    """The re-bucketing half of the rank-elastic engine (DESIGN.md §2.12):
    the same optimizer config at a new (global or per-group) rank -- fresh
    specs, fresh ``BucketPlan``/``StateLayout`` for the new
    ``(d, n, rank, dtype)`` keys, fresh jittable update.  Live state does
    NOT carry over automatically; migrate it with
    ``core.rank_schedule.migrate_opt_state`` before feeding it to the
    rebuilt optimizer.  ``lowrank_filter`` must match the one the original
    optimizer was built with (the default filter when None)."""
    kw: Dict[str, Any] = {}
    if rank is not None:
        kw["rank"] = rank
        kw["group_ranks"] = ()
    if group_ranks is not None:
        kw["group_ranks"] = tuple(group_ranks)
    if not kw:
        raise ValueError("rebuild_at_rank needs rank or group_ranks")
    cfg = dataclasses.replace(optimizer.config, **kw)
    return make_lowrank_optimizer(cfg, params_like, lowrank_filter)


def current_ranks(optimizer: "LowRankOptimizer") -> Tuple[int, Tuple[int, ...]]:
    """(global rank, per-group ranks) the optimizer was built at -- the
    schedule state a checkpoint carries so resume rebuilds the same
    bucket geometry before loading."""
    cfg = optimizer.config
    groups = max(cfg.refresh_groups, 1)
    if cfg.group_ranks:
        return max(cfg.group_ranks), tuple(cfg.group_ranks)
    return cfg.rank, (cfg.rank,) * groups


def _safe_ratio(num: jax.Array, den: jax.Array) -> jax.Array:
    nn = jnp.linalg.norm(num.astype(jnp.float32).reshape(-1))
    dd = jnp.linalg.norm(den.astype(jnp.float32).reshape(-1))
    return nn / (dd + 1e-12)


def project_grads(
    optimizer: "LowRankOptimizer", grads: PyTree, state: LowRankOptState
) -> PyTree:
    """Project low-rank leaves into R-space using the *current* projectors.

    The distributed project-then-reduce path calls this on per-shard local
    gradients, then psums the (much smaller) result; by linearity
    psum(P^T G_local) == P^T psum(G_local) since P is replicated.
    """
    is_spec = lambda x: isinstance(x, LeafSpec)  # noqa: E731
    flat_specs, treedef = jax.tree_util.tree_flatten(
        optimizer.specs, is_leaf=is_spec
    )
    flat_states = treedef.flatten_up_to(state.leaves)
    flat_grads = treedef.flatten_up_to(grads)
    stacked_projs = {}
    if optimizer.state_layout is not None and state.buckets:
        # bucket-native state: per-leaf projector views sliced from stacks
        stacked_projs = buckets_lib.leaf_projectors(
            optimizer.state_layout, state.buckets
        )
    out = []
    for i, (spec, st, g) in enumerate(zip(flat_specs, flat_states, flat_grads)):
        if spec.lowrank:
            proj = stacked_projs.get(i, st.projector)
            out.append(proj_lib.project(g, proj, spec.side))
        else:
            out.append(g)
    return jax.tree_util.tree_unflatten(treedef, out)


def _flatten_for_buckets(optimizer: "LowRankOptimizer", grads: PyTree):
    """(flat_grads, rest tuple) in the optimizer's static leaf order."""
    is_spec = lambda x: isinstance(x, LeafSpec)  # noqa: E731
    _, treedef = jax.tree_util.tree_flatten(optimizer.specs, is_leaf=is_spec)
    flat_grads = treedef.flatten_up_to(grads)
    bucketed = optimizer.bucket_plan.bucketed
    rest = tuple(
        g for i, g in enumerate(flat_grads) if i not in bucketed
    )
    return flat_grads, rest


def _require_bucket_native(optimizer: "LowRankOptimizer", what: str):
    if optimizer.state_layout is None:
        raise ValueError(
            f"{what} needs a bucket-native optimizer (engine='bucketed' "
            "with a fused inner, no Fira); the reference engine uses the "
            "per-leaf project_grads path"
        )


def project_grads_stacked(
    optimizer: "LowRankOptimizer",
    grads: PyTree,
    state: LowRankOptState,
    shard_axes: Optional[Tuple[str, ...]] = None,
) -> StackedGrads:
    """Bucket-native project-then-reduce payload: one batched ``P^T G``
    per bucket, producing f32 ``(B, r, n)`` R-space stacks straight from
    the bucket projector buffers (kernels/galore_project's batch grid on
    TPU, batched einsum elsewhere).

    The distributed path psums the returned structure -- ONE contiguous
    operand per bucket plus the full-rank leaves -- then hands it to
    ``optimizer.update(..., projected=True)`` unchanged: R-space
    gradients never round-trip through per-leaf layout.  By linearity
    psum(P^T G_local) == P^T psum(G_local) since P is replicated.
    """
    _require_bucket_native(optimizer, "project_grads_stacked")
    if not state.buckets:
        raise ValueError(
            "bucket-native optimizer got a canonical per-leaf state; "
            "convert with storage_opt_state(optimizer, state)"
        )
    flat_grads, rest = _flatten_for_buckets(optimizer, grads)
    layout = optimizer.state_layout
    bucket_states = state.buckets
    projectors = None
    if layout.shards > 1:
        if shard_axes is not None:
            # shard-local state: every replica must project ALL B rows of
            # its local gradient before the reduce-scatter, so the full
            # projector stacks are all-gathered (the ZeRO per-step price,
            # modeled in dp_comm_model's zero_hot schedule).
            projectors = buckets_lib.zero_gather_projectors(
                layout, bucket_states, shard_axes
            )
        else:
            # replicated padded representation: drop the inert pad rows
            projectors = [
                bst.projector
                for bst in buckets_lib.zero_unpad_states(
                    layout, bucket_states
                )
            ]
    stacks = buckets_lib.bucketed_project_grads(
        layout.plan, bucket_states, flat_grads, projectors=projectors
    )
    return StackedGrads(buckets=stacks, rest=rest)


def stack_grads(optimizer: "LowRankOptimizer", grads: PyTree) -> StackedGrads:
    """Full-rank gradients in bucket-native layout: one ``(B, d, n)``
    stack per bucket (canonical orientation) plus the non-bucketed
    leaves.  The compressed-DP refresh step psums this form -- same bytes
    as the per-leaf tree, one operand per bucket -- and
    ``optimizer.update(..., refresh=True)`` consumes the stacks directly
    (``bucketed_refresh`` slices hot entries out instead of
    re-concatenating leaves)."""
    _require_bucket_native(optimizer, "stack_grads")
    flat_grads, rest = _flatten_for_buckets(optimizer, grads)
    stacks = buckets_lib.bucketed_stack_grads(
        optimizer.state_layout.plan, flat_grads
    )
    return StackedGrads(buckets=stacks, rest=rest)


# ---------------------------------------------------------------------------
# state-layout conversion (DESIGN.md §2.5): storage <-> canonical per-leaf
# ---------------------------------------------------------------------------


def canonical_opt_state(
    optimizer: "LowRankOptimizer", state: LowRankOptState
) -> LowRankOptState:
    """Storage layout -> canonical per-leaf layout (the checkpoint format).

    Pure re-layout (reshape/transpose/split, no arithmetic): the returned
    state has the exact pytree structure a ``engine="reference"``
    optimizer would produce, so checkpoints written from a bucket-native
    run load under any engine, bit-for-bit.  No-op when the state is
    already canonical.
    """
    layout = optimizer.state_layout
    if layout is None or not state.buckets:
        return state
    # zero-sharded layouts store padded stacks; the canonical layout drops
    # the inert pad rows first, so checkpoints are identical across
    # state_shards settings (resume is bit-identical and cross-engine).
    per_leaf = buckets_lib.bucketed_to_leaf_states(
        layout, buckets_lib.zero_unpad_states(layout, state.buckets)
    )
    is_spec = lambda x: isinstance(x, LeafSpec)  # noqa: E731
    _, treedef = jax.tree_util.tree_flatten(optimizer.specs, is_leaf=is_spec)
    flat_states = treedef.flatten_up_to(state.leaves)
    out = []
    for i, st in enumerate(flat_states):
        if i in per_leaf:
            proj, inner_state = per_leaf[i]
            out.append(LeafState(projector=proj, inner=inner_state))
        else:
            out.append(st)
    leaves = jax.tree_util.tree_unflatten(treedef, out)
    return LowRankOptState(
        step=state.step, key=state.key, leaves=leaves, buckets=()
    )


def storage_opt_state(
    optimizer: "LowRankOptimizer", state: LowRankOptState
) -> LowRankOptState:
    """Canonical per-leaf layout -> the optimizer's storage layout.

    Inverse of ``canonical_opt_state``: stacks the moments/projectors of
    every bucketed leaf and empties the per-leaf slots.  No-op for
    per-leaf-storage optimizers or states that are already bucket-native.
    """
    layout = optimizer.state_layout
    if layout is None or state.buckets:
        return state
    is_spec = lambda x: isinstance(x, LeafSpec)  # noqa: E731
    _, treedef = jax.tree_util.tree_flatten(optimizer.specs, is_leaf=is_spec)
    flat_states = treedef.flatten_up_to(state.leaves)
    bucket_states = buckets_lib.zero_pad_states(
        layout, buckets_lib.leaf_states_to_bucketed(layout, flat_states)
    )
    out = [
        _placeholder_leaf() if i in layout.plan.bucketed else st
        for i, st in enumerate(flat_states)
    ]
    leaves = jax.tree_util.tree_unflatten(treedef, out)
    return LowRankOptState(
        step=state.step, key=state.key, leaves=leaves, buckets=bucket_states
    )


def apply_updates(params: PyTree, updates: PyTree) -> PyTree:
    return jax.tree_util.tree_map(
        lambda p, u: (p + u.astype(p.dtype)), params, updates
    )


def state_memory_bytes(state: LowRankOptState) -> int:
    """Total bytes held in optimizer state (the paper's memory claim)."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(state):
        total += leaf.size * leaf.dtype.itemsize
    return int(total)


def optimizer_memory_report(
    params: PyTree, state: LowRankOptState
) -> Dict[str, float]:
    pbytes = sum(
        l.size * l.dtype.itemsize for l in jax.tree_util.tree_leaves(params)
    )
    sbytes = state_memory_bytes(state)
    return {
        "param_bytes": float(pbytes),
        "opt_state_bytes": float(sbytes),
        "state_to_param_ratio": float(sbytes) / float(max(pbytes, 1)),
    }
