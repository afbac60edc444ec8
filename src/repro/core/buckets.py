"""The bucketed fused update engine (DESIGN.md §2.3) and its state layout
(DESIGN.md §2.5).

``engine="reference"`` (lowrank.py's per-leaf loop) runs a separate
project -> inner-update -> back-project einsum chain per low-rank leaf and
then a *second* full pass over params in ``apply_updates``, materializing
every full-space direction in HBM.  This module is the
``engine="bucketed"`` hot path:

  * at build time, ``build_bucket_plan`` groups low-rank leaves by their
    canonical (d, n, rank, dtype) -- the side='right' leaves enter
    transposed, so e.g. a (96, 32) down-projection and a (32, 96)
    up-projection land in the SAME bucket;
  * ``build_state_layout`` turns the plan into a **storage** decision:
    when the inner optimizer is fused-eligible, moments and projectors
    *live* in the per-bucket stacked (B, r, n) / (B, d, r) layout as
    ``BucketState`` buffers (``LowRankOptState.buckets``) instead of
    per-leaf ``LeafState`` arrays -- the hot step never stacks/unstacks
    optimizer state, only params and grads (which the model owns);
  * per step, each bucket's param/grad leaves are stacked into (B, d, n)
    operands (stacked scan/expert leaves reshape in for free) and ONE
    batched fused kernel per bucket computes

        R  = P^T G                      (skipped when grads arrive projected)
        W' = (1 - lr*wd) W - lr*alpha * P @ N(inner(R))

    directly -- the full-space direction never touches HBM, params are
    read/written exactly once (kernels/lowrank_update), and the moment
    buffers are consumed/produced in their storage layout (donation
    reuses them in place).  On non-TPU backends the same bucketed shape
    runs as batched einsums (ops.py), so the dispatch-count win and the
    numerics are identical everywhere.

The *refresh* executable is bucket-native too (DESIGN.md §2.6):
``bucketed_refresh`` runs all same-group entries of a bucket as ONE
batched randomized-subspace-iteration chain over their stacked (B', d, n)
gradients whenever the projector config is batchable, with per-slice RNG
keys that replicate the per-leaf schedule bit-for-bit; the exact SVD
backend falls back to the per-leaf loop (paper-faithful runs untouched).

Checkpoints never see the stacked layout: ``bucketed_to_leaf_states`` /
``leaf_states_to_bucketed`` convert between the storage layout and the
canonical per-leaf layout (exact reshapes/transposes/concats, no
arithmetic), so a run checkpointed under one engine resumes bit-for-bit
under the other (train/checkpoint.py applies the converters on save/load).
"""
from __future__ import annotations

from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import inner as inner_lib
from repro.kernels.lowrank_update import ops as update_ops
from repro.kernels.lowrank_update import quantize as qz

PyTree = Any

# Inner optimizers with a fused kernel (kernels/lowrank_update/kernel.py).
FUSED_INNERS = ("adam", "msgd", "adam8bit", "adam_mini")

# Inners whose storage layout is orientation-sensitive: adam_mini's
# per-row v and adam8bit's per-row-chunk scales follow the PER-LEAF rows,
# which a mixed left/right bucket cannot stack into one buffer.  Their
# bucket plans split by side (``build_bucket_plan(split_sides=True)``) so
# every bucket is side-homogeneous; adam/msgd keep the mixed buckets.
SIDE_HOMOGENEOUS_INNERS = ("adam8bit", "adam_mini")


class BucketEntry(NamedTuple):
    """One low-rank leaf's slot inside a bucket (static)."""

    leaf_idx: int  # index into the flattened spec/param lists
    side: str  # 'left' | 'right' (right enters the stack transposed)
    batch: int  # stacked slices contributed (prod of leading dims, >= 1)


class Bucket(NamedTuple):
    """Leaves sharing canonical oriented dims -- one fused dispatch."""

    d: int  # projected dim (= min(m, n) of every member)
    n: int  # free dim after orientation
    rank: int
    entries: Tuple[BucketEntry, ...]
    # 'left' | 'right' for side-homogeneous plans (split_sides=True);
    # 'any' when the bucket may mix sides (adam / msgd plans).
    side: str = "any"


    @property
    def batch(self) -> int:
        return sum(e.batch for e in self.entries)


class BucketPlan(NamedTuple):
    buckets: Tuple[Bucket, ...]
    bucketed: frozenset  # leaf indices the buckets cover

    def num_dispatches(self, projected: bool = False) -> int:
        """Fused ops per hot step (project + update, or update only)."""
        return len(self.buckets) * (1 if projected else 2)


def build_bucket_plan(
    flat_specs: Sequence,
    flat_params: Sequence,
    *,
    split_sides: bool = False,
) -> BucketPlan:
    """Static bucketing: group low-rank leaves by (d, n, rank, dtype).

    ``split_sides=True`` adds the projection side to the key (and stamps it
    on the bucket) for the orientation-sensitive quantized inners
    (``SIDE_HOMOGENEOUS_INNERS``) -- a (96, 32) down-projection then gets
    its own bucket instead of sharing the (32, 96) up-projection's.

    The per-leaf effective rank is clamped to ``min(d, n)`` HERE, at plan
    time: a spec whose rank exceeds the projected dim (tiny leaves under a
    large configured rank) must not bake an impossible (d, r) projector
    shape into the bucket key -- that surfaces later as an opaque kernel
    shape failure.  ``build_specs`` applies the same clamp, so for specs it
    built this is a no-op; plans built from hand-rolled specs get the same
    guarantee.  A rank < 1 is a configuration error and raises.
    """
    groups: Dict[Tuple, List[BucketEntry]] = {}
    for i, (spec, leaf) in enumerate(zip(flat_specs, flat_params)):
        if not spec.lowrank:
            continue
        m, n = leaf.shape[-2], leaf.shape[-1]
        d_c, n_c = (m, n) if spec.side == "left" else (n, m)
        if spec.rank < 1:
            raise ValueError(
                f"bucket plan: leaf {i} ({spec.path!r}, shape "
                f"{tuple(leaf.shape)}) has rank {spec.rank}; rank must be "
                ">= 1 for every low-rank leaf"
            )
        eff_rank = min(spec.rank, d_c)
        b = 1
        for s in leaf.shape[:-2]:
            b *= s
        key = (d_c, n_c, eff_rank, jnp.dtype(leaf.dtype).name)
        if split_sides:
            key = key + (spec.side,)
        groups.setdefault(key, []).append(BucketEntry(i, spec.side, b))
    buckets = tuple(
        Bucket(
            d=k[0], n=k[1], rank=k[2], entries=tuple(es),
            side=k[4] if split_sides else "any",
        )
        for k, es in sorted(groups.items(), key=lambda kv: kv[0])
    )
    covered = frozenset(e.leaf_idx for bk in buckets for e in bk.entries)
    return BucketPlan(buckets=buckets, bucketed=covered)


# ---------------------------------------------------------------------------
# storage layout: bucket-native optimizer state
# ---------------------------------------------------------------------------


class BucketState(NamedTuple):
    """One bucket's optimizer state in storage (stacked) layout.

    ``projector`` is (B, d, r) in canonical orientation (projectors are
    (d, r) for BOTH sides, never transposed); moments are (B, r, n) in
    the canonical 'left' orientation (side='right' slices enter
    transposed, exactly like the param/grad operands).  Per inner
    optimizer (DESIGN.md §2.5/§2.8):

      adam       m, v       (B, r, n) f32
      msgd       m          (B, r, n) f32; v is None
      adam_mini  m          (B, r, n) f32; v is the per-row second moment
                 -- (B, r) for 'left' buckets, (B, n) for 'right' ones
                 (per-leaf rows; the reduction axis transposes with the
                 slices, so buckets are side-homogeneous for this inner)
      adam8bit   m, v       (B, r, n) uint8 codes element-aligned with the
                 canonical stack; ``m_scale``/``v_scale`` hold the f32
                 per-row-chunk scales in per-leaf row order -- (B, r, nb)
                 'left', (B, n, nb_r) 'right' (quantize.py's partition).

    ``m_scale``/``v_scale`` are None for the unquantized inners.
    """

    projector: jax.Array
    m: jax.Array
    v: Optional[jax.Array]
    m_scale: Optional[jax.Array] = None
    v_scale: Optional[jax.Array] = None


class LeafStateTemplate(NamedTuple):
    """Per-leaf canonical shapes/dtypes (static) -- what the per-leaf
    layout stores and what checkpoints serialize."""

    projector: jax.ShapeDtypeStruct
    m: jax.ShapeDtypeStruct
    v: Optional[jax.ShapeDtypeStruct]
    m_scale: Optional[jax.ShapeDtypeStruct] = None
    v_scale: Optional[jax.ShapeDtypeStruct] = None


class StateLayout(NamedTuple):
    """Build-time decision that the optimizer state is bucket-native,
    plus everything needed to convert in BOTH directions (save/load).

    ``shards > 1`` selects the ZeRO-style DP-sharded layout
    (``state_sharding="zero"``, DESIGN.md §2.10): every stack is padded
    along the leading ``B`` dim to a multiple of ``shards`` with inert
    zero rows, so each DP replica can own exactly ``B_pad / shards``
    contiguous rows of every buffer.  The padded layout is an internal
    representation only -- checkpoints always serialize the canonical
    per-leaf layout, which unpads first.
    """

    plan: BucketPlan
    inner_name: str  # 'adam' | 'msgd' | 'adam_mini' | 'adam8bit'
    has_v: bool
    templates: Dict[int, LeafStateTemplate]  # keyed by leaf_idx (static)
    shards: int = 1  # 1 = replicated; >1 = zero-sharded over the DP axis


def build_state_layout(
    plan: BucketPlan,
    flat_specs: Sequence,
    flat_params: Sequence,
    *,
    inner_name: str,
    projector_dtype,
    shards: int = 1,
) -> StateLayout:
    """Canonical per-leaf templates for every bucketed leaf."""
    if shards < 1:
        raise ValueError(f"shards must be >= 1, got {shards}")
    has_v = inner_lib.fused_has_second_moment(inner_name)
    if inner_name in SIDE_HOMOGENEOUS_INNERS:
        for bucket in plan.buckets:
            if bucket.side not in ("left", "right"):
                raise ValueError(
                    f"{inner_name!r} needs a side-homogeneous bucket plan "
                    "(build_bucket_plan(split_sides=True))"
                )
    templates: Dict[int, LeafStateTemplate] = {}
    for bucket in plan.buckets:
        for e in bucket.entries:
            p = flat_params[e.leaf_idx]
            lead = p.shape[:-2]
            proj = jax.ShapeDtypeStruct(
                lead + (bucket.d, bucket.rank), jnp.dtype(projector_dtype)
            )
            if e.side == "left":
                mshape = lead + (bucket.rank, p.shape[-1])
            else:
                mshape = lead + (p.shape[-2], bucket.rank)
            m_scale = v_scale = None
            if inner_name == "adam8bit":
                m = jax.ShapeDtypeStruct(mshape, jnp.uint8)
                v = m
                nb = qz.num_blocks(mshape[-1])
                m_scale = jax.ShapeDtypeStruct(
                    mshape[:-1] + (nb,), jnp.float32
                )
                v_scale = m_scale
            elif inner_name == "adam_mini":
                m = jax.ShapeDtypeStruct(mshape, jnp.float32)
                v = jax.ShapeDtypeStruct(mshape[:-1], jnp.float32)
            else:
                m = jax.ShapeDtypeStruct(mshape, jnp.float32)
                v = m if has_v else None
            templates[e.leaf_idx] = LeafStateTemplate(
                proj, m, v, m_scale, v_scale
            )
    return StateLayout(
        plan=plan, inner_name=inner_name, has_v=has_v, templates=templates,
        shards=shards,
    )


def init_bucket_states(layout: StateLayout) -> Tuple[BucketState, ...]:
    """Stacked equivalent of the per-leaf init: eye projectors (the first
    refresh installs the real ones), zero moments (quantized zeros for
    adam8bit -- identical codes/scales to ``inner.adam8bit().init``).

    With ``layout.shards > 1`` the stacks come back zero-padded to the
    sharded row count (``zero_pad_states``)."""
    out = []
    for bucket in layout.plan.buckets:
        B, d, n, r = bucket.batch, bucket.d, bucket.n, bucket.rank
        pdtype = layout.templates[bucket.entries[0].leaf_idx].projector.dtype
        eye = jnp.broadcast_to(jnp.eye(d, r, dtype=pdtype), (B, d, r))
        if layout.inner_name == "adam8bit":
            z = jnp.zeros((B, r, n), jnp.float32)
            mc, ms = qz.quantize_stacked(z, bucket.side, signed=True)
            vc, vs = qz.quantize_stacked(z, bucket.side, signed=False)
            out.append(BucketState(
                projector=eye, m=mc, v=vc, m_scale=ms, v_scale=vs
            ))
            continue
        m = jnp.zeros((B, r, n), jnp.float32)
        if layout.inner_name == "adam_mini":
            rows = r if bucket.side == "left" else n
            v = jnp.zeros((B, rows), jnp.float32)
        else:
            v = jnp.zeros((B, r, n), jnp.float32) if layout.has_v else None
        out.append(BucketState(projector=eye, m=m, v=v))
    return zero_pad_states(layout, out)


def leaf_states_to_bucketed(
    layout: StateLayout, flat_states: Sequence
) -> Tuple[BucketState, ...]:
    """Per-leaf canonical -> storage: stack projectors and moments.

    ``flat_states`` holds objects with ``.projector`` and ``.inner`` at the
    bucketed indices; other entries are ignored.  Pure layout:
    reshape/transpose/concat only -- quantized codes transpose like
    moments (elementwise layout), scales and per-row v buffers stack in
    per-leaf row order with no transpose, so nothing is re-quantized.
    """
    out = []
    for bucket in layout.plan.buckets:
        proj = _gather_proj(
            bucket, [getattr(st, "projector", None) for st in flat_states]
        )
        fm: Dict[int, inner_lib.FusedMoments] = {
            e.leaf_idx: inner_lib.fused_moments(
                layout.inner_name, flat_states[e.leaf_idx].inner
            )
            for e in bucket.entries
        }
        m = _gather(bucket, {i: x.m for i, x in fm.items()})
        m_scale = v_scale = v = None
        if layout.inner_name == "adam8bit":
            v = _gather(bucket, {i: x.v for i, x in fm.items()})
            m_scale = _gather_proj(
                bucket, {i: x.m_scale for i, x in fm.items()}
            )
            v_scale = _gather_proj(
                bucket, {i: x.v_scale for i, x in fm.items()}
            )
        elif layout.inner_name == "adam_mini":
            v = _gather_vec(bucket, {i: x.v for i, x in fm.items()})
        elif layout.has_v:
            v = _gather(bucket, {i: x.v for i, x in fm.items()})
        out.append(BucketState(
            projector=proj, m=m, v=v, m_scale=m_scale, v_scale=v_scale
        ))
    return tuple(out)


def bucketed_to_leaf_states(
    layout: StateLayout, bucket_states: Sequence[BucketState]
) -> Dict[int, Tuple[jax.Array, Any]]:
    """Storage -> per-leaf canonical: {leaf_idx: (projector, inner_state)}.

    Inverse of ``leaf_states_to_bucketed`` (exact; no arithmetic).
    """
    out: Dict[int, Tuple[jax.Array, Any]] = {}
    for bucket, bst in zip(layout.plan.buckets, bucket_states):
        tmpl = {e.leaf_idx: layout.templates[e.leaf_idx]
                for e in bucket.entries}
        projs = _scatter_proj(
            bucket, bst.projector, {i: t.projector for i, t in tmpl.items()}
        )
        ms = _scatter(bucket, bst.m, {i: t.m for i, t in tmpl.items()})
        vs = mss = vss = None
        if layout.inner_name == "adam8bit":
            vs = _scatter(bucket, bst.v, {i: t.v for i, t in tmpl.items()})
            mss = _scatter_proj(
                bucket, bst.m_scale, {i: t.m_scale for i, t in tmpl.items()}
            )
            vss = _scatter_proj(
                bucket, bst.v_scale, {i: t.v_scale for i, t in tmpl.items()}
            )
        elif layout.inner_name == "adam_mini":
            vs = _scatter_proj(
                bucket, bst.v, {i: t.v for i, t in tmpl.items()}
            )
        elif layout.has_v:
            vs = _scatter(bucket, bst.v, {i: t.v for i, t in tmpl.items()})
        for e in bucket.entries:
            i = e.leaf_idx
            inner_state = inner_lib.fused_state(
                layout.inner_name,
                ms[i],
                vs[i] if vs is not None else None,
                mss[i] if mss is not None else None,
                vss[i] if vss is not None else None,
            )
            out[i] = (projs[i], inner_state)
    return out


def leaf_projectors(
    layout: StateLayout, bucket_states: Sequence[BucketState]
) -> Dict[int, jax.Array]:
    """Per-leaf projector views sliced out of the stacks (no transpose --
    projectors are canonical (d, r) for both sides)."""
    out: Dict[int, jax.Array] = {}
    for bucket, bst in zip(layout.plan.buckets, bucket_states):
        out.update(_scatter_proj(
            bucket, bst.projector,
            {e.leaf_idx: layout.templates[e.leaf_idx].projector
             for e in bucket.entries},
        ))
    return out


# ---------------------------------------------------------------------------
# ZeRO-style DP-sharded state layout (state_sharding="zero", DESIGN.md §2.10)
# ---------------------------------------------------------------------------
#
# Each (B, ...) stack is padded along dim 0 to B_pad = ceil(B/shards)*shards
# so every DP replica owns a contiguous (B_pad/shards, ...) row block of
# every buffer.  Pad rows are INERT by construction: every fused inner is
# row-independent along the leading dim, all pad inputs (params, grads,
# moments) are zero, and zero rows are fixed points of every update --
# adam/msgd/adam_mini trivially (0 moments + 0 grads -> 0 direction), and
# adam8bit because dequantize maps both the zero-padded codes (scale 0) and
# the re-quantized zero rows (codes for 0, scale 1) to exactly 0.0
# (quantize.py clamps absmax 0 -> scale 1).  Canonical (checkpoint)
# conversion always unpads first, so pad-row bit patterns never escape.


def zero_padded_batch(batch: int, shards: int) -> int:
    """Smallest multiple of ``shards`` >= ``batch``."""
    return -(-batch // shards) * shards


def _pad_rows(x: jax.Array, rows: int) -> jax.Array:
    if x.shape[0] == rows:
        return x
    pad = [(0, rows - x.shape[0])] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, pad)


def _map_state(bst: BucketState, fn) -> BucketState:
    return BucketState(*[None if x is None else fn(x) for x in bst])


def zero_pad_states(
    layout: StateLayout, bucket_states: Sequence[BucketState]
) -> Tuple[BucketState, ...]:
    """Canonical-batch stacks -> padded sharded-layout stacks (zero rows)."""
    if layout.shards <= 1:
        return tuple(bucket_states)
    out = []
    for bucket, bst in zip(layout.plan.buckets, bucket_states):
        bp = zero_padded_batch(bucket.batch, layout.shards)
        out.append(_map_state(bst, lambda x, bp=bp: _pad_rows(x, bp)))
    return tuple(out)


def zero_unpad_states(
    layout: StateLayout, bucket_states: Sequence[BucketState]
) -> Tuple[BucketState, ...]:
    """Padded sharded-layout stacks -> canonical-batch stacks (drop pads)."""
    if layout.shards <= 1:
        return tuple(bucket_states)
    return tuple(
        _map_state(bst, lambda x, b=bucket.batch: x[:b])
        for bucket, bst in zip(layout.plan.buckets, bucket_states)
    )


def zero_pad_grad_stacks(
    layout: StateLayout, stacks: Sequence[jax.Array]
) -> Tuple[jax.Array, ...]:
    """Zero-pad per-bucket gradient stacks to the padded (shardable) batch.

    The padded stacks are what the per-bucket ``psum_scatter`` consumes:
    the pad rows are zeros on every replica, so the scattered slice of a
    pad row is exactly zero and the matching (inert) state pad rows stay
    fixed points of the fused update.
    """
    return tuple(
        _pad_rows(x, zero_padded_batch(bucket.batch, layout.shards))
        for bucket, x in zip(layout.plan.buckets, stacks)
    )


def zero_shard_index(axis_names: Sequence[str]) -> jax.Array:
    """Combined shard index over the DP axes, matching the row order of a
    tiled ``psum_scatter``/``all_gather`` applied over the same axis tuple
    (major-to-minor in the given order)."""
    idx = jnp.int32(0)
    for a in axis_names:
        idx = idx * jax.lax.psum(1, a) + jax.lax.axis_index(a)
    return idx


def zero_local_states(
    layout: StateLayout,
    bucket_states: Sequence[BucketState],
    shard_index: jax.Array,
) -> Tuple[BucketState, ...]:
    """Slice one shard's contiguous row block out of full padded stacks
    (traced ``shard_index`` -- usable inside shard_map)."""
    out = []
    for bucket, bst in zip(layout.plan.buckets, bucket_states):
        rows = zero_padded_batch(bucket.batch, layout.shards) // layout.shards
        out.append(_map_state(
            bst,
            lambda x, rows=rows: jax.lax.dynamic_slice_in_dim(
                x, shard_index * rows, rows, axis=0
            ),
        ))
    return tuple(out)


def zero_gather_states(
    local_states: Sequence[BucketState], axis_names: Sequence[str]
) -> Tuple[BucketState, ...]:
    """all_gather shard-local stacks back to the full PADDED layout (tiled
    along dim 0, inverse of the ``zero_local_states`` slicing)."""
    return tuple(
        _map_state(
            bst,
            lambda x: jax.lax.all_gather(
                x, tuple(axis_names), axis=0, tiled=True
            ),
        )
        for bst in local_states
    )


def zero_gather_projectors(
    layout: StateLayout,
    local_states: Sequence[BucketState],
    axis_names: Sequence[str],
) -> Tuple[jax.Array, ...]:
    """Full UNPADDED (B, d, r) projector stacks from shard-local state.

    The hot-path projection P^T G runs over all B rows of the local
    gradient contribution (every replica sees different data, so every
    replica must project every row before the reduce-scatter) -- this
    per-step projector all-gather is the ZeRO price of sharding the
    projector stacks, and is modeled in ``dp_comm_model``'s zero schedule.
    """
    return tuple(
        jax.lax.all_gather(
            bst.projector, tuple(axis_names), axis=0, tiled=True
        )[: bucket.batch]
        for bucket, bst in zip(layout.plan.buckets, local_states)
    )


def zero_local_param_stacks(
    layout: StateLayout,
    flat_params: Sequence[jax.Array],
    shard_index: jax.Array,
) -> Tuple[jax.Array, ...]:
    """This shard's (B_pad/shards, d, n) row block of every W stack.

    Params are replicated, so the slice is free of communication: gather
    the canonical stack per-leaf, zero-pad, take the local rows.
    """
    out = []
    for bucket in layout.plan.buckets:
        bp = zero_padded_batch(bucket.batch, layout.shards)
        rows = bp // layout.shards
        w = _pad_rows(_gather(bucket, flat_params), bp)
        out.append(jax.lax.dynamic_slice_in_dim(
            w, shard_index * rows, rows, axis=0
        ))
    return tuple(out)


def zero_gather_stacks(
    layout: StateLayout,
    local_stacks: Sequence[jax.Array],
    axis_names: Sequence[str],
) -> Tuple[jax.Array, ...]:
    """all_gather per-bucket local row blocks into full UNPADDED stacks --
    the W' gather of the zero hot step (pad rows dropped)."""
    return tuple(
        jax.lax.all_gather(x, tuple(axis_names), axis=0, tiled=True)[
            : bucket.batch
        ]
        for bucket, x in zip(layout.plan.buckets, local_stacks)
    )


def zero_scatter_outputs(
    plan: BucketPlan,
    stacks: Sequence[jax.Array],
    flat_params: Sequence,
) -> Dict[int, jax.Array]:
    """Full (B, d, n) output stacks -> {leaf_idx: per-leaf array} (the
    per-leaf scatter ``bucketed_update`` skips under ``out_stacked``)."""
    out: Dict[int, jax.Array] = {}
    for bucket, s in zip(plan.buckets, stacks):
        out.update(_scatter(bucket, s, flat_params))
    return out


# ---------------------------------------------------------------------------
# stack / unstack
# ---------------------------------------------------------------------------


def _orient_in(x: jax.Array, side: str) -> jax.Array:
    """Leaf -> (b, a, b') canonical stack slices (side='right' transposed)."""
    x2 = x.reshape((-1,) + x.shape[-2:])
    if side == "right":
        x2 = jnp.swapaxes(x2, -1, -2)
    return x2


def _gather(bucket: Bucket, leaves) -> jax.Array:
    """``leaves`` is anything indexable by leaf_idx (list or dict)."""
    parts = [_orient_in(leaves[e.leaf_idx], e.side) for e in bucket.entries]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


def _gather_proj(bucket: Bucket, projs) -> jax.Array:
    """Plain (never-transposed) stack of 2-trailing-dim buffers: projectors
    ((.., d, r) for BOTH sides) and the quantized scale buffers (already in
    per-leaf row order).  ``projs`` is anything indexable by leaf_idx."""
    parts = [
        projs[e.leaf_idx].reshape((-1,) + projs[e.leaf_idx].shape[-2:])
        for e in bucket.entries
    ]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


def _gather_vec(bucket: Bucket, leaves) -> jax.Array:
    """Stack of 1-trailing-dim buffers (adam_mini's per-row v)."""
    parts = [
        leaves[e.leaf_idx].reshape((-1,) + leaves[e.leaf_idx].shape[-1:])
        for e in bucket.entries
    ]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


def _scatter(
    bucket: Bucket, stacked: jax.Array, likes
) -> Dict[int, jax.Array]:
    """Split a (B, ...) result back into per-leaf arrays shaped like
    ``likes[leaf_idx]`` (orientation and dtype restored; ``likes`` is any
    leaf_idx-indexable of shape/dtype carriers, arrays or structs)."""
    out: Dict[int, jax.Array] = {}
    off = 0
    for e in bucket.entries:
        part = stacked[off : off + e.batch]
        off += e.batch
        if e.side == "right":
            part = jnp.swapaxes(part, -1, -2)
        like = likes[e.leaf_idx]
        out[e.leaf_idx] = part.reshape(like.shape).astype(like.dtype)
    return out


def _scatter_proj(
    bucket: Bucket, stacked: jax.Array, likes: Dict[int, Any]
) -> Dict[int, jax.Array]:
    """Split a plain (never-transposed) stack per leaf: projectors, the
    quantized scale buffers, and adam_mini's (B, rows) per-row v --
    ``reshape(like.shape)`` restores any trailing rank."""
    out: Dict[int, jax.Array] = {}
    off = 0
    for e in bucket.entries:
        part = stacked[off : off + e.batch]
        off += e.batch
        like = likes[e.leaf_idx]
        out[e.leaf_idx] = part.reshape(like.shape).astype(like.dtype)
    return out


# ---------------------------------------------------------------------------
# the fused hot-path update (bucket-native state)
# ---------------------------------------------------------------------------


def bucketed_project_grads(
    plan: BucketPlan,
    bucket_states: Sequence[BucketState],
    flat_grads: Sequence[jax.Array],
    projectors: Optional[Sequence[jax.Array]] = None,
) -> Tuple[jax.Array, ...]:
    """Per-bucket batched projection: one ``(B, r, n)`` R-space gradient
    stack per bucket, straight from the bucket projector buffers.

    This is the distributed project-then-reduce payload: ONE contiguous
    f32 buffer per bucket to psum instead of a ragged per-leaf tree
    (kernels/galore_project's batch grid on TPU, batched einsum elsewhere).

    ``projectors`` overrides the per-bucket (B, d, r) stacks -- the
    zero-sharded path passes the all-gathered full projectors here
    (``zero_gather_projectors``) since local state only holds a row slice.
    """
    if projectors is None:
        projectors = [bst.projector for bst in bucket_states]
    return tuple(
        update_ops.bucketed_project(_gather(bucket, flat_grads), proj)
        for bucket, proj in zip(plan.buckets, projectors)
    )


def bucketed_stack_grads(
    plan: BucketPlan, flat_grads: Sequence[jax.Array]
) -> Tuple[jax.Array, ...]:
    """Per-bucket stacked ``(B, d, n)`` FULL gradients (canonical
    orientation) -- the refresh-step reduce payload; ``bucketed_refresh``
    and the fused update consume the stacks directly."""
    return tuple(_gather(bucket, flat_grads) for bucket in plan.buckets)


def bucketed_all_finite(
    plan: BucketPlan,
    flat_grads: Optional[Sequence[jax.Array]] = None,
    stacked_grads: Optional[Sequence[jax.Array]] = None,
) -> List[jax.Array]:
    """Per-bucket scalar ``all(isfinite(stack))`` -- the skip-step gate.

    ONE fused reduction per bucket over the contiguous gradient stack
    (never a per-leaf loop): with ``stacked_grads`` given (the compressed-DP
    payload, ``(B, r, n)`` or ``(B, d, n)``) the check reads the stacks the
    update consumes anyway; otherwise the stacks come from ``_gather``,
    which XLA CSEs against the identical gathers inside ``bucketed_update``
    so the leaves are still read once.  Non-bucketed leaves are the
    caller's (cheap, few) responsibility.
    """
    if stacked_grads is not None:
        stacks = stacked_grads
    else:
        stacks = [_gather(bucket, flat_grads) for bucket in plan.buckets]
    return [jnp.all(jnp.isfinite(s)) for s in stacks]


def _unstack_entry(
    stacked: jax.Array, bucket: Bucket, entry: BucketEntry, template
) -> jax.Array:
    """One entry's per-leaf view out of a full-gradient ``(B, d, n)`` stack
    (orientation restored, leading batch dims reshaped back)."""
    off = 0
    for e in bucket.entries:
        if e.leaf_idx == entry.leaf_idx:
            break
        off += e.batch
    part = stacked[off : off + entry.batch]
    if entry.side == "right":
        part = jnp.swapaxes(part, -1, -2)
    lead = template.projector.shape[:-2]
    return part.reshape(lead + part.shape[-2:])


def bucketed_update(
    plan: BucketPlan,
    cfg,  # OptimizerConfig
    bucket_states: Sequence[BucketState],
    flat_grads: Sequence[jax.Array],
    flat_params: Sequence[jax.Array],
    step: jax.Array,
    lr: jax.Array,
    *,
    projected: bool,
    apply: bool,
    track_norm: bool = True,
    stacked_grads: Optional[Sequence[jax.Array]] = None,
    stacked_params: Optional[Sequence[jax.Array]] = None,
    out_stacked: bool = False,
) -> Tuple[Any, Tuple[BucketState, ...], List[jax.Array]]:
    """Run every bucket against its *storage-layout* state.

    Returns ``({leaf_idx: new_param_or_update}, new_bucket_states,
    per_bucket_norm_sq)``.  Moments and projectors are consumed/produced
    in place in the stacked layout -- the only per-step stack/unstack is
    of params and grads (which the model owns per-leaf).

    ``stacked_grads`` (one array per bucket, already in canonical stacked
    orientation) short-circuits the per-leaf gather: the distributed
    project-then-reduce path hands the psum'd ``(B, r, n)`` R-space stacks
    (``projected=True``) or the psum'd full ``(B, d, n)`` stacks (refresh
    steps) straight to the engine, so compressed gradients never
    round-trip through per-leaf layout.

    ``apply=True`` returns the new parameter leaf (the kernel's W' output);
    ``apply=False`` returns the additive update W' - W.  ``track_norm``
    gates the ``aux.update_norm`` W' - W read pass
    (OptimizerConfig.track_update_norm).

    The ZeRO-sharded hot path (DESIGN.md §2.10) hands shard-local row
    blocks of every operand -- ``stacked_grads`` AND ``stacked_params``
    (pre-sliced W stacks) -- and sets ``out_stacked=True`` to get the W'
    stacks back unscattered (one per bucket, for the caller's all-gather)
    instead of the per-leaf dict.  Every fused inner is row-independent
    along the leading dim, so local slices go through the identical
    kernels.
    """
    lr_alpha = lr * cfg.alpha
    lr_wd = lr * cfg.weight_decay if cfg.weight_decay else 0.0
    ik = cfg.inner_kwargs()
    out_leaves: Dict[int, jax.Array] = {}
    out_stacks: List[jax.Array] = []
    new_states: List[BucketState] = []
    norm_sq: List[jax.Array] = []
    for bi, (bucket, bst) in enumerate(zip(plan.buckets, bucket_states)):
        w = (stacked_params[bi] if stacked_params is not None
             else _gather(bucket, flat_params))
        p = bst.projector
        if projected:
            r_g = (stacked_grads[bi] if stacked_grads is not None
                   else _gather(bucket, flat_grads))
        else:
            g = (stacked_grads[bi] if stacked_grads is not None
                 else _gather(bucket, flat_grads))
            r_g = update_ops.bucketed_project(g, p)
        if cfg.inner == "msgd":
            w_new, m_new = update_ops.bucketed_msgd_update(
                w, p, r_g, bst.m, lr_alpha, lr_wd, **ik
            )
            new_bst = BucketState(projector=p, m=m_new, v=None)
        elif cfg.inner == "adam_mini":
            w_new, m_new, v_new = update_ops.bucketed_adam_mini_update(
                w, p, r_g, bst.m, bst.v, step, lr_alpha, lr_wd,
                side=bucket.side, **ik,
            )
            new_bst = BucketState(projector=p, m=m_new, v=v_new)
        elif cfg.inner == "adam8bit":
            w_new, mc, ms, vc, vs = update_ops.bucketed_adam8bit_update(
                w, p, r_g, bst.m, bst.m_scale, bst.v, bst.v_scale,
                step, lr_alpha, lr_wd, side=bucket.side, **ik,
            )
            new_bst = BucketState(
                projector=p, m=mc, v=vc, m_scale=ms, v_scale=vs
            )
        else:
            w_new, m_new, v_new = update_ops.bucketed_adam_update(
                w, p, r_g, bst.m, bst.v, step, lr_alpha, lr_wd, **ik
            )
            new_bst = BucketState(projector=p, m=m_new, v=v_new)
        out = w_new if apply else w_new - w
        if track_norm:
            delta = (w_new - w) if apply else out
            norm_sq.append(jnp.sum(jnp.square(delta.astype(jnp.float32))))
        if out_stacked:
            out_stacks.append(out)
        else:
            out_leaves.update(_scatter(bucket, out, flat_params))
        new_states.append(new_bst)
    return (out_stacks if out_stacked else out_leaves), tuple(new_states), norm_sq


# ---------------------------------------------------------------------------
# the refresh path on stacked operands
# ---------------------------------------------------------------------------


def _entry_slice_keys(subkey: jax.Array, entry: BucketEntry, template):
    """The per-slice PRNG keys one entry contributes to a batched refresh.

    EXACTLY the per-leaf schedule of ``projectors.refresh_projector``: the
    leaf key folds the *global* leaf index; a leaf with leading batch dims
    splits it over the flattened slices, a plain 2-D leaf uses it whole.
    Returns a (entry.batch, ...) stacked key array.
    """
    lkey = jax.random.fold_in(subkey, entry.leaf_idx)
    if template.projector.shape[:-2]:
        return jax.random.split(lkey, entry.batch)
    return lkey[None]


def bucketed_refresh(
    layout: StateLayout,
    bucket_states: Sequence[BucketState],
    flat_specs: Sequence,
    flat_grads: Sequence[jax.Array],
    subkey: jax.Array,
    refresh_fn,  # (g, key, old_p, spec) -> new per-leaf projector
    *,
    group: int,
    momentum_carry: str,
    stacked_refresh_fn=None,  # (g_stack, keys, old_p_stack, rank) -> stack
    stacked_grads: Optional[Sequence[jax.Array]] = None,
) -> Tuple[Tuple[BucketState, ...], List[jax.Array]]:
    """Refresh the projectors of one static refresh ``group`` directly in
    the bucket stacks.

    With ``stacked_refresh_fn`` (the batched refresh engine, provided when
    ``projectors.batched_refresh_supported`` covers the config): ALL of a
    bucket's same-group entries refresh as ONE batched chain over their
    stacked (B', d, n) gradients -- batched Gaussian sketch, fused power
    iterations, batched thin QR, one small batched ``eigh`` (the Gram
    matrix of B = Q^T G), batched Gumbel top-k -- instead of a chain per
    leaf.  Per-slice keys follow the exact per-leaf schedule
    (``_entry_slice_keys``), so the batched stack is bit-identical to the
    per-leaf fallback, which remains for the exact backend
    (``stacked_refresh_fn=None``): slice each refreshed entry's old
    projector out of the stack, run the per-leaf ``refresh_fn``, and
    concatenate the new slices back.

    Either way the scatter into the (B, d, r) stack is static, and the
    ``momentum_carry="reproject"`` carry (M' = P_new^T P_old M) runs as ONE
    batched r x r einsum over the whole stack instead of a per-leaf loop;
    non-refreshed slices keep their exact old moments (static selection,
    not a where over approximate C ~= I).

    ``stacked_grads`` (one canonical ``(B, d, n)`` stack per bucket, e.g.
    the psum'd payload of the compressed-DP refresh step) short-circuits
    the per-leaf gather: hot-entry gradients are sliced out of the stack
    instead of re-concatenated from leaves.

    Returns (new_bucket_states, per-leaf overlap diagnostics).  Keys fold
    the *global* leaf index, so trajectories are bit-identical with the
    reference engine's per-leaf refresh.
    """
    new_states: List[BucketState] = []
    overlaps: List[jax.Array] = []
    for bi, (bucket, bst) in enumerate(zip(layout.plan.buckets,
                                           bucket_states)):
        parts: List[jax.Array] = []
        refreshed: List[bool] = []
        if stacked_refresh_fn is not None:
            hot = [
                e for e in bucket.entries
                if flat_specs[e.leaf_idx].group == group
            ]
            new_slices: Dict[int, jax.Array] = {}
            if hot:
                if stacked_grads is not None:
                    g_stack = _slice_entries(bucket, stacked_grads[bi], hot)
                else:
                    g_stack = _gather(bucket._replace(entries=tuple(hot)),
                                      flat_grads)
                old_stack = _slice_entries(bucket, bst.projector, hot)
                keys = jnp.concatenate([
                    _entry_slice_keys(
                        subkey, e, layout.templates[e.leaf_idx]
                    )
                    for e in hot
                ], axis=0)
                new_stack = stacked_refresh_fn(
                    g_stack, keys, old_stack, bucket.rank
                ).astype(bst.projector.dtype)
                # overlap diagnostic (GARD18): ||P_new^T P_old||_F^2 / r
                # per slice, averaged per LEAF like the reference path.
                c = jnp.einsum("bdn,bdo->bno", new_stack, old_stack)
                vals = (
                    jnp.sum(c.astype(jnp.float32) ** 2, axis=(-2, -1))
                    / bucket.rank
                )
                off_h = 0
                for e in hot:
                    overlaps.append(jnp.mean(vals[off_h : off_h + e.batch]))
                    new_slices[e.leaf_idx] = (
                        new_stack[off_h : off_h + e.batch]
                    )
                    off_h += e.batch
            off = 0
            for e in bucket.entries:
                old_slice = bst.projector[off : off + e.batch]
                off += e.batch
                if e.leaf_idx in new_slices:
                    parts.append(new_slices[e.leaf_idx])
                    refreshed.append(True)
                else:
                    parts.append(old_slice)
                    refreshed.append(False)
        else:
            off = 0
            for e in bucket.entries:
                old_slice = bst.projector[off : off + e.batch]
                off += e.batch
                spec = flat_specs[e.leaf_idx]
                if spec.group == group:
                    tmpl = layout.templates[e.leaf_idx].projector
                    old_p = old_slice.reshape(tmpl.shape)
                    lkey = jax.random.fold_in(subkey, e.leaf_idx)
                    if stacked_grads is not None:
                        g_leaf = _unstack_entry(
                            stacked_grads[bi], bucket, e,
                            layout.templates[e.leaf_idx],
                        )
                    else:
                        g_leaf = flat_grads[e.leaf_idx]
                    new_p = refresh_fn(g_leaf, lkey, old_p, spec)
                    # overlap diagnostic (GARD18): ||P_new^T P_old||_F^2 /
                    # r, same per-leaf reduction as the reference path.
                    c = jnp.einsum("...dn,...do->...no", new_p, old_p)
                    overlaps.append(jnp.mean(
                        jnp.sum(c.astype(jnp.float32) ** 2, axis=(-2, -1))
                        / spec.rank
                    ))
                    parts.append(
                        new_p.reshape((-1,) + new_p.shape[-2:])
                        .astype(bst.projector.dtype)
                    )
                    refreshed.append(True)
                else:
                    parts.append(old_slice)
                    refreshed.append(False)
        new_proj = parts[0] if len(parts) == 1 else jnp.concatenate(parts, 0)

        m, v = bst.m, bst.v
        ms_, vs_ = bst.m_scale, bst.v_scale
        if any(refreshed):
            if momentum_carry == "reset":
                # reference semantics: the WHOLE inner state resets (m and
                # second moment -- for adam8bit, codes AND scales) for
                # refreshed leaves.
                m = _select_slices(bucket, refreshed, jnp.zeros_like(m), m)
                if v is not None:
                    v = _select_slices(
                        bucket, refreshed, jnp.zeros_like(v), v
                    )
                if ms_ is not None:
                    ms_ = _select_slices(
                        bucket, refreshed, jnp.zeros_like(ms_), ms_
                    )
                if vs_ is not None:
                    vs_ = _select_slices(
                        bucket, refreshed, jnp.zeros_like(vs_), vs_
                    )
            elif momentum_carry == "reproject" and (
                layout.inner_name != "adam8bit"
            ):
                # C = P_new^T P_old for every slice, then M' = C M: two
                # batched einsums per bucket.  In canonical orientation the
                # single left-side formula covers both sides exactly
                # (side='right' moments are stored transposed).  adam8bit
                # is excluded: its first moment lives as quantized codes,
                # which have no linear reprojection -- exactly the
                # reference path's behavior (Adam8bitState has no ``.m``
                # for ``_refresh_leaf`` to reproject), stated in §2.8.
                c = jnp.einsum("bdn,bdo->bno", new_proj, bst.projector)
                # m stays f32 (the einsum promotes c), matching the
                # reference path's precision exactly.
                m2 = jnp.einsum("bno,bok->bnk", c, m).astype(m.dtype)
                m = _select_slices(bucket, refreshed, m2, m)
        new_states.append(BucketState(
            projector=new_proj, m=m, v=v, m_scale=ms_, v_scale=vs_
        ))
    return tuple(new_states), overlaps


def _slice_entries(
    bucket: Bucket, stacked: jax.Array, entries: Sequence[BucketEntry]
) -> jax.Array:
    """Concatenated stack slices of an entry subset (in bucket order)."""
    want = frozenset(e.leaf_idx for e in entries)
    parts = []
    off = 0
    for e in bucket.entries:
        if e.leaf_idx in want:
            parts.append(stacked[off : off + e.batch])
        off += e.batch
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, 0)


def _select_slices(
    bucket: Bucket, take_new: Sequence[bool], new: jax.Array, old: jax.Array
) -> jax.Array:
    """Static per-entry selection between two stacked buffers."""
    if all(take_new):
        return new
    parts = []
    off = 0
    for e, t in zip(bucket.entries, take_new):
        parts.append((new if t else old)[off : off + e.batch])
        off += e.batch
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, 0)


# ---------------------------------------------------------------------------
# analytic accounting (benchmarks/kernels_micro.update_engine_bench)
# ---------------------------------------------------------------------------


def _moment_traffic_bytes(bk: Bucket, inner: str, engine: str) -> int:
    """Moment-buffer HBM traffic of one hot step for one bucket.

    adam: M, V f32 read + write.  msgd: M only.  adam_mini: M r/w + the
    per-row v statistic's extra R read (it crosses n-blocks, so the engine
    reads the R stack once more) + the tiny v r/w.  adam8bit fused: uint8
    codes r/w for both moments + scales -- the f32 moments live only in
    VMEM.  adam8bit on the reference path ALSO materializes the dequantized
    f32 M and V as XLA temporaries (write + read each): that round-trip is
    exactly what the fused kernel deletes.
    """
    B, n, r = bk.batch, bk.n, bk.rank
    rn = B * r * n * 4
    if inner == "msgd":
        return 2 * rn
    if inner == "adam_mini":
        rows = r if bk.side != "right" else n
        return 2 * rn + rn + 2 * B * rows * 4
    if inner == "adam8bit":
        rows, rowlen = (r, n) if bk.side != "right" else (n, r)
        codes = 4 * B * r * n  # M, V codes read + write, 1 byte each
        scales = 4 * B * rows * qz.num_blocks(rowlen) * 4
        if engine != "bucketed":
            codes += 4 * rn  # dequantized f32 M, V temporaries, w + r
        return codes + scales
    return 4 * rn  # adam


def modeled_hbm_bytes(
    plan: BucketPlan,
    engine: str,
    itemsize: int = 4,
    projected: bool = False,
    state_layout: str = "bucketed",
    track_update_norm: bool = False,
    inner: str = "adam",
) -> int:
    """Modeled optimizer-path HBM traffic per hot step for the bucketed
    leaves (moment traffic per ``inner`` -- see ``_moment_traffic_bytes``;
    default adam keeps the pre-§2.8 numbers).

    reference: G read (project) + R written+read, moments r/w, direction N
    materialized d x n (write + read), params read + update written, then
    ``apply_updates``'s second pass (param read + update read + param
    write).
    bucketed: G read once, R written+read once (inter-kernel), P read
    twice, moments r/w once, params read+written once.  No N, no second
    pass.  ``state_layout="perleaf"`` adds the per-step moment
    stack/unstack (read per-leaf + write stacked, and back) and the
    projector stack that bucket-native storage deletes;
    ``track_update_norm`` adds the W' - W re-read for ``aux.update_norm``.
    """
    total = 0
    for bk in plan.buckets:
        B, d, n, r = bk.batch, bk.d, bk.n, bk.rank
        wn = B * d * n * itemsize
        pr = B * d * r * 4
        rn = B * r * n * 4
        moments = _moment_traffic_bytes(bk, inner, engine)
        if engine == "bucketed":
            proj = 0 if projected else (wn + pr + rn)  # read G,P; write R
            upd = wn + pr + rn + moments + wn  # W r, P, R, moments, W' w
            extra = 0
            if state_layout == "perleaf":
                # stack: read per-leaf + write stacked; unstack: the
                # reverse -- 2 extra r/w passes per moment buffer, plus
                # the projector stack (read + write, consumed stacked).
                extra += 2 * moments + 2 * pr
            if track_update_norm:
                extra += 2 * wn  # re-read W' and W for ||W' - W||
            total += proj + upd + extra
        else:
            proj = 0 if projected else (wn + pr + rn)
            inner_tr = rn + moments  # R read, moments r/w
            direction = rn + 2 * rn  # N = f(M', V') r-space write + read
            backproj = pr + rn + 2 * wn  # P, N_r -> full-space dir d x n
            apply = 3 * wn  # params read + dir read + params write
            total += proj + inner_tr + direction + backproj + apply
    return total


def modeled_state_bytes(
    plan: BucketPlan, inner: str = "adam", shards: int = 1
) -> Dict[str, float]:
    """Modeled RESIDENT optimizer-state bytes of the bucketed leaves (the
    paper's Table-1 memory claim, per storage layout §2.5/§2.8): projector
    stacks (f32) + moment buffers.  ``moment_bytes_per_param`` is the
    moment cost per low-rank R-space element -- 8.0 for adam (two f32
    moments), ~2.0 for adam8bit (two uint8 code planes + scales).

    ``shards > 1`` additionally models the zero-sharded layout
    (§2.10): ``padded_total`` is the global padded footprint and
    ``per_device`` what one DP replica actually holds
    (``padded_total / shards`` -- the ZeRO memory win, ~``1/shards`` of
    ``total`` up to row padding)."""
    projectors = 0
    moments = 0
    n_elems = 0
    per_device = 0
    padded_total = 0
    for bk in plan.buckets:
        B, d, n, r = bk.batch, bk.d, bk.n, bk.rank
        row_proj = d * r * 4
        if inner == "msgd":
            row_mom = r * n * 4
        elif inner == "adam_mini":
            rows = r if bk.side != "right" else n
            row_mom = r * n * 4 + rows * 4
        elif inner == "adam8bit":
            rows, rowlen = (r, n) if bk.side != "right" else (n, r)
            row_mom = 2 * r * n + 2 * rows * qz.num_blocks(rowlen) * 4
        else:
            row_mom = 2 * r * n * 4
        # NB: adam_mini's per-row v and adam8bit's scales are per STACK row
        # along B, so per-row bytes are exact for both layouts.
        projectors += B * row_proj
        moments += B * row_mom
        n_elems += B * r * n
        bp = zero_padded_batch(B, shards)
        padded_total += bp * (row_proj + row_mom)
        per_device += (bp // shards) * (row_proj + row_mom)
    return {
        "total": float(projectors + moments),
        "projectors": float(projectors),
        "moments": float(moments),
        "moment_bytes_per_param": moments / max(n_elems, 1),
        "shards": float(shards),
        "padded_total": float(padded_total),
        "per_device": float(per_device),
    }


def sharded_ckpt_model(
    plan: BucketPlan, inner: str = "adam", shards: int = 1
) -> Dict[str, float]:
    """Modeled checkpoint WRITE payload of the bucketed optimizer state
    (DESIGN.md §2.11): ``canonical_bytes`` is what the single-writer
    canonical format serializes (every byte through one host after the
    gather/unpad converters), ``sharded_bytes_per_host`` what one writer
    of the shard-parallel format puts on disk (its ``padded_total /
    shards`` row block of every stack -- the same 1/shards factor as the
    resident-memory win, up to row padding).  ``stack_files_per_host`` is
    the per-writer file (save-op) count: one ``.npy`` per bucket per live
    BucketState field per owned shard.  Params and non-bucketed state are
    excluded -- they are replicated in both formats and cancel in the
    comparison the bench gates."""
    if inner == "msgd":
        fields = 2  # projector + m
    elif inner == "adam8bit":
        fields = 5  # projector + m/v code planes + m/v scale stacks
    else:
        fields = 3  # projector + m + v (adam, adam_mini's per-row v)
    st = modeled_state_bytes(plan, inner, shards)
    return {
        "canonical_bytes": st["total"],
        "sharded_bytes_per_host": st["padded_total"] / max(shards, 1),
        "stack_files_per_host": float(len(plan.buckets) * fields),
        "shards": float(shards),
    }


def update_num_ops(
    plan: BucketPlan, inner: str = "adam", projected: bool = False
) -> int:
    """Dispatched ops per bucketed hot step: projection (unless grads
    arrive projected) + the fused update per bucket, plus adam_mini's
    per-row v statistic (one small jnp reduction per bucket -- it crosses
    n-blocks, so it cannot fold into the kernel grid)."""
    per_bucket = (1 if projected else 2)
    if inner == "adam_mini":
        per_bucket += 1
    return len(plan.buckets) * per_bucket


def reference_num_ops(
    plan: BucketPlan, projected: bool = False, inner: str = "adam"
) -> int:
    """Per-leaf chain length on the reference path: project, moment update,
    direction, back-project (+ the apply_updates add) per low-rank leaf;
    adam8bit adds the dequant and requant passes, adam_mini the per-row
    statistic."""
    n_leaves = sum(len(bk.entries) for bk in plan.buckets)
    per_leaf = 4 if projected else 5
    if inner == "adam8bit":
        per_leaf += 2
    elif inner == "adam_mini":
        per_leaf += 1
    return n_leaves * per_leaf


def finite_check_model(
    plan: BucketPlan, projected: bool = False, itemsize: int = 4
) -> Dict[str, float]:
    """Modeled cost of the skip-step gate (``bucketed_all_finite``): one
    fused ``all(isfinite)`` reduction per bucket stack, reading the
    ``(B, r, n)`` R-space stacks on the projected hot path or the full
    ``(B, d, n)`` stacks otherwise.  The read is a re-read of buffers the
    update consumes in the same executable, so on TPU it is HBM-bandwidth
    bound with zero extra writes -- the overhead the recovery bench gates
    (benchmarks/kernels_micro.recovery_overhead_bench)."""
    nbytes = 0
    for bk in plan.buckets:
        rows = bk.rank if projected else bk.d
        nbytes += bk.batch * rows * bk.n * itemsize
    return {
        "modeled_hbm_bytes": float(nbytes),
        "dispatched_ops": float(len(plan.buckets)),
    }


# ---------------------------------------------------------------------------
# refresh accounting (benchmarks/kernels_micro.refresh_engine_bench)
# ---------------------------------------------------------------------------
#
# Both models describe the RANDOMIZED (sara/dominant) refresh chain:
#
#   perleaf -- the PRE-batched-engine baseline of record: one chain per
#   refreshed leaf, classic two-QR HMT iteration with the (n, k')
#   intermediate Z = G^T Q materialized in HBM and re-orthonormalized.
#   NOTE this is deliberately NOT what ``batched_refresh=False`` dispatches
#   today -- the per-leaf randomized SVD was restructured onto the fused
#   thin-QR chain in the same change, so the current fallback costs
#   7 + 2q ops per leaf, not 7 + 4q.  The model pins the baseline this
#   engine replaced so cross-PR --check comparisons don't shift.
#
#   batched -- the bucket-native engine: ONE chain per bucket with refreshed
#   entries, thin-QR-only iterations, Z held in VMEM (kernels/power_iter),
#   plus the honest concat cost of stacking the hot entries' gradients.


def _refresh_chain_ops(engine: str, power_iters: int) -> int:
    """Dispatched ops of one chain: sketch draw + sketch GEMM + final QR +
    B = Q^T G GEMM + Gram ``eigh`` + Gumbel sample + column gather (7),
    plus per power iteration either QR + fused power step (batched, 2) or
    QR + Z GEMM + QR + Y GEMM (perleaf, 4).  ``power_iters`` is the
    post-clamp count -- callers apply ``svd.clamp_sketch`` per bucket so
    the gated numbers match what actually dispatches."""
    per_iter = 2 if engine == "batched" else 4
    return 7 + per_iter * power_iters


def refresh_num_ops(
    plan: BucketPlan,
    flat_specs: Sequence,
    *,
    engine: str,
    group: int = 0,
    oversample: int = 8,
    power_iters: int = 2,
    pool_factor: int = 4,
) -> int:
    """Modeled dispatched-op count of one randomized (SARA-pool) refresh
    step of ``group`` -- same clamping as ``modeled_refresh_hbm_bytes``,
    so buckets whose full-range sketch skips the power iterations at
    runtime are counted without them here too."""
    from repro.core import svd as svd_lib

    total = 0
    for bk in plan.buckets:
        k = min(bk.d, pool_factor * bk.rank)
        _, _, iters = svd_lib.clamp_sketch(
            bk.d, bk.n, k, oversample, power_iters
        )
        chain = _refresh_chain_ops(engine, iters)
        n_hot = sum(
            1 for e in bk.entries
            if flat_specs[e.leaf_idx].group == group
        )
        total += chain * (min(n_hot, 1) if engine == "batched" else n_hot)
    return total


def modeled_refresh_hbm_bytes(
    plan: BucketPlan,
    flat_specs: Sequence,
    *,
    engine: str,
    group: int = 0,
    oversample: int = 8,
    power_iters: int = 2,
    pool_factor: int = 4,
    itemsize: int = 4,
) -> int:
    """Modeled HBM traffic of one randomized (SARA-pool) refresh step.

    Per refreshed (d, n) slice with sketch width k' (pool + oversample,
    degenerate shapes clamped exactly like ``svd.clamp_sketch``): sketch
    GEMM, the power iterations (engine-dependent, see module comment --
    the batched engine's fused kernel deletes the 2 n k' Z round-trip and
    one n-side QR per iteration), final QR, B = Q^T G, the ``eigh`` of
    its (k', k') Gram matrix, U = Q U_b, and the sampled (d, r) projector
    write-back.  The batched engine additionally pays the gradient concat
    for multi-entry buckets.
    """
    from repro.core import svd as svd_lib

    total = 0
    for bk in plan.buckets:
        d, n, r = bk.d, bk.n, bk.rank
        k = min(d, pool_factor * r)
        _, kp, iters = svd_lib.clamp_sketch(d, n, k, oversample, power_iters)
        dn, dkp, nkp = d * n, d * kp, n * kp
        per_slice = dn + nkp + dkp  # sketch: G read, omega read, Y write
        if engine == "batched":
            # thin QR (Y r/w) + fused step (G read twice, Q read, Y write)
            per_slice += iters * (2 * dkp + 2 * dn + 2 * dkp)
        else:
            # QR(Y) + Z = G^T Q (HBM write) + QR(Z) + Y = G Z
            per_slice += iters * (2 * dkp + (dn + dkp + nkp)
                                  + 2 * nkp + (dn + nkp + dkp))
        per_slice += 2 * dkp  # final QR
        per_slice += dkp + dn + nkp  # B = Q^T G
        per_slice += nkp + kp * kp + kp  # Gram of B and its eigh
        per_slice += 2 * dkp + kp * kp  # U = Q @ U_b
        per_slice += kp + d * r  # spectrum read + sampled projector write
        hot = [
            e for e in bk.entries if flat_specs[e.leaf_idx].group == group
        ]
        n_slices = sum(e.batch for e in hot)
        bucket_bytes = n_slices * per_slice
        # _gather concatenates only when >1 HOT entry stacks (a single
        # refreshed entry -- e.g. staggered groups -- slices for free)
        if engine == "batched" and len(hot) > 1:
            bucket_bytes += 2 * n_slices * dn  # gradient stack concat r/w
        total += bucket_bytes * itemsize
    return total


# ---------------------------------------------------------------------------
# DP gradient-reduction accounting (compressed project-then-reduce)
# ---------------------------------------------------------------------------


def dp_comm_model(
    plan: BucketPlan,
    flat_params: Sequence,
    *,
    axis_sizes: Optional[Dict[str, int]] = None,
    state_shards: int = 1,
    inner: str = "adam",
    rank_plans: Optional[Sequence[Tuple[float, BucketPlan]]] = None,
) -> Dict[str, Any]:
    """Modeled per-replica DP gradient-reduction payload per step.

    Schedules (bytes = per-replica collective operand bytes, collectives =
    reduction operands dispatched before XLA combining):

    * ``standard``            -- every gradient leaf reduces full-rank,
      one operand per leaf (what SPMD inserts for the uncompressed step);
    * ``compressed_hot``      -- low-rank leaves reduce as ONE contiguous
      f32 ``(B, r, n)`` R-space stack per bucket (project-then-reduce);
      full-rank leaves unchanged.  The low-rank payload shrinks by exactly
      d/r per bucket;
    * ``compressed_refresh``  -- low-rank leaves reduce full-rank but
      stacked: same bytes as standard, one operand per bucket;
    * ``zero_hot``            -- ``state_sharding="zero"`` hot step
      (``state_shards > 1``): R-space stacks reduce-scatter (padded rows),
      plus the per-step all-gathers the sharded state forces -- full
      projector stacks before projection and the updated W' row slices
      after the local update.  ``reduce_scatter_bytes`` /
      ``all_gather_bytes`` break the total down;
    * ``zero_refresh``        -- refresh under zero sharding: full stacks
      all-reduce (as ``compressed_refresh``) plus the one-shot all-gather
      of every padded state stack so the batched refresh can run on full
      buckets (amortized over ``tau`` steps).

    Full-rank grads count at their param dtype; R-space stacks are f32
    (what ``bucketed_project`` emits).  With ``axis_sizes`` (e.g.
    ``{"pod": 2, "data": 16}``) every schedule gains a ``per_axis``
    decomposition of a hierarchical reduction: ``intra_pod_bytes`` is the
    operand processed on intra-pod links (reduce-scatter + all-gather
    stage), ``inter_pod_bytes`` the already-scattered shard crossing the
    pod boundary (``payload / data``).  The ``pod`` compressed mode
    (train/step ``compressed="pod"``) is the hierarchy where intra-pod
    stays full-rank and only the compressed stacks cross pods -- reported
    as top-level ``pod_mode_hot``.  Recorded by ``launch/dryrun.py`` and
    regression-gated via ``benchmarks/kernels_micro``'s
    ``dp_compression_bench``.
    """
    rest_bytes = 0
    n_rest = 0
    for i, leaf in enumerate(flat_params):
        if i in plan.bucketed:
            continue
        rest_bytes += leaf.size * jnp.dtype(leaf.dtype).itemsize
        n_rest += 1
    lowrank_full = 0
    lowrank_rspace = 0
    n_lowrank_leaves = 0
    rs_rspace_pad = 0  # padded R-space reduce-scatter payload
    ag_proj = 0  # full projector-stack all-gather
    ag_w = 0  # updated W' row-slice all-gather
    for bk in plan.buckets:
        dt = jnp.dtype(flat_params[bk.entries[0].leaf_idx].dtype).itemsize
        for e in bk.entries:
            leaf = flat_params[e.leaf_idx]
            lowrank_full += (
                e.batch * bk.d * bk.n
                * jnp.dtype(leaf.dtype).itemsize
            )
            n_lowrank_leaves += 1
        lowrank_rspace += bk.batch * bk.rank * bk.n * 4
        bp = zero_padded_batch(bk.batch, max(state_shards, 1))
        rs_rspace_pad += bp * bk.rank * bk.n * 4
        ag_proj += bp * bk.d * bk.rank * 4
        ag_w += bp * bk.d * bk.n * dt
    state_gather = modeled_state_bytes(
        plan, inner=inner, shards=max(state_shards, 1)
    )["padded_total"]
    out: Dict[str, Any] = {
        "standard": {
            "bytes": rest_bytes + lowrank_full,
            "collectives": n_rest + n_lowrank_leaves,
        },
        "compressed_hot": {
            "bytes": rest_bytes + lowrank_rspace,
            "collectives": n_rest + len(plan.buckets),
        },
        "compressed_refresh": {
            "bytes": rest_bytes + lowrank_full,
            "collectives": n_rest + len(plan.buckets),
        },
        "lowrank_bytes_standard": lowrank_full,
        "lowrank_bytes_compressed_hot": lowrank_rspace,
        "lowrank_compression_ratio": (
            lowrank_full / lowrank_rspace if lowrank_rspace else 1.0
        ),
    }
    if state_shards > 1:
        out["zero_hot"] = {
            "bytes": rest_bytes + rs_rspace_pad + ag_proj + ag_w,
            "collectives": n_rest + 3 * len(plan.buckets),
            "reduce_scatter_bytes": rs_rspace_pad,
            "all_gather_bytes": ag_proj + ag_w,
        }
        stacks_per_bucket = 2 + (inner != "msgd") + 2 * (inner == "adam8bit")
        out["zero_refresh"] = {
            "bytes": rest_bytes + lowrank_full + int(state_gather),
            "collectives": n_rest
            + len(plan.buckets) * (1 + stacks_per_bucket),
            "state_gather_bytes": int(state_gather),
        }
        out["modeled_state_bytes_per_device"] = modeled_state_bytes(
            plan, inner=inner, shards=state_shards
        )["per_device"]
    if axis_sizes:
        data_n = int(axis_sizes.get("data", 1))
        pod_n = int(axis_sizes.get("pod", 1))
        for key in ("standard", "compressed_hot", "compressed_refresh",
                    "zero_hot", "zero_refresh"):
            if key not in out:
                continue
            payload = out[key]["bytes"]
            out[key]["per_axis"] = {
                "intra_pod_bytes": payload if data_n > 1 else 0,
                "inter_pod_bytes": (
                    payload // data_n if pod_n > 1 else 0
                ),
            }
        # compressed="pod": the data axis reduces full-rank per-leaf (plain
        # SPMD inside the pod); only the compressed stacks cross pods.
        out["pod_mode_hot"] = {
            "intra_pod_bytes": out["standard"]["bytes"] if data_n > 1 else 0,
            "inter_pod_bytes": (
                out["compressed_hot"]["bytes"] if pod_n > 1 else 0
            ),
        }
    if rank_plans:
        # Schedule-aware resident-state model (DESIGN.md §2.12): the rank
        # schedule holds a sequence of static-rank segments, each with its
        # own bucket plan.  ``rank_plans`` is ``[(weight, plan), ...]``
        # with weights summing to 1 (fraction of training spent in that
        # segment, core/rank_schedule.schedule_plan_weights); peak is the
        # provisioning number, the time-weighted average the actual
        # memory-integral win over a static run at the peak rank.
        seg_bytes = [
            (w, modeled_state_bytes(p, inner=inner,
                                    shards=max(state_shards, 1))["total"])
            for w, p in rank_plans
        ]
        wsum = sum(w for w, _ in seg_bytes) or 1.0
        out["modeled_state_bytes_peak"] = max(b for _, b in seg_bytes)
        out["modeled_state_bytes_avg"] = (
            sum(w * b for w, b in seg_bytes) / wsum
        )
    return out
