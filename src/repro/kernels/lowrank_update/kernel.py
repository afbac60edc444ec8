"""Pallas TPU kernels: fused low-rank (Adam | MSGD) update + back-projection.

The torch GaLore update runs four separate passes over HBM per layer:
moment update (read M,V,R / write M,V), Adam direction (read M,V / write N),
back-projection GEMM (read P,N / write dW), weight update (read W,dW/write W).
This kernel fuses all four: per (batch, n-block, d-block) grid step it

  * at d==0: updates the (r, bn) moment slabs in VMEM, writes the new
    moments, and stashes the bias-corrected direction N in a VMEM scratch;
  * for every d: computes  W'[d-blk, n-blk] = (1 - lr*wd) W - lr_alpha *
    P[d-blk] @ N straight out of the scratch -- the full-space direction
    (d x n) is never materialized in HBM, weight decay rides along for free,
    and W' *replaces* the separate ``apply_updates`` pass (params are read
    and written exactly once).

Grid: (batch, n_blocks, d_blocks), d innermost so the N scratch computed at
d==0 is reused by all d-blocks of the same (batch, n-block) (TPU grid steps
run sequentially, scratch persists).  r (<= 512) is kept whole in VMEM:
P block (bd, r) and N scratch (r, bn) are both 128-aligned MXU operands.

The leading batch dimension is a real grid axis (not vmap-of-pallas_call):
the bucketed update engine (core/buckets.py) stacks every same-shape leaf of
a pytree into one (B, d, n) tensor and dispatches ONE kernel per bucket.
B == 1 recovers the single-matrix kernel; the 2-D entry points below are
thin reshaping wrappers.

Scalar operands (the bias corrections ``1 - b**step``, lr_alpha, lr_wd)
arrive via scalar prefetch so no retrace happens when the step or the
learning-rate schedule moves.  The bias corrections are computed by XLA in
the wrapper, exactly as the jnp references compute them: Mosaic cannot
lower a power with a traced exponent.

Four inner optimizers are fused (DESIGN.md §2.3/§2.8): ``adam`` (M, V
moments, bias-corrected), ``msgd`` (single moment, the optimizer of
Theorem 3.4), ``adam8bit`` (blockwise uint8 codes + f32 scales dequantized
/ requantized inside the moment phase, so the f32 moments never touch
HBM), and ``adam_mini`` (per-row second moment; the tiny cross-n row
statistic is computed by the caller, the kernel consumes the resulting
denominator).  Quantized variants take a static ``side``: their scale /
per-row layouts follow the PER-LEAF orientation while the stacked operands
are canonical (side='right' buckets are side-homogeneous by construction).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import compat
from repro.kernels.lowrank_update.quantize import QBLOCK, num_blocks


# ---------------------------------------------------------------------------
# Adam
# ---------------------------------------------------------------------------


def _adam_scalars(step, lr_alpha, lr_wd, b1: float, b2: float) -> jax.Array:
    """(4,) f32 scalar-prefetch operand [1-b1^t, 1-b2^t, lr_alpha, lr_wd]."""
    t = step.astype(jnp.float32)
    return jnp.stack([
        1.0 - b1**t,
        1.0 - b2**t,
        jnp.asarray(lr_alpha, jnp.float32),
        jnp.asarray(lr_wd, jnp.float32),
    ])


def _adam_kernel(
    scalars,  # SMEM: (4,) f32 [bc1, bc2, lr_alpha, lr_wd]
    w_ref,  # (1, bd, bn) in
    p_ref,  # (1, bd, r)
    r_ref,  # (1, r, bn)
    m_ref,  # (1, r, bn)
    v_ref,  # (1, r, bn)
    w_out,  # (1, bd, bn)
    m_out,  # (1, r, bn)
    v_out,  # (1, r, bn)
    n_scr,  # VMEM scratch (r, bn) f32
    *,
    b1: float,
    b2: float,
    eps: float,
):
    i_d = pl.program_id(2)

    @pl.when(i_d == 0)
    def _update_moments():
        r32 = r_ref[0].astype(jnp.float32)
        m_new = b1 * m_ref[0].astype(jnp.float32) + (1.0 - b1) * r32
        v_new = b2 * v_ref[0].astype(jnp.float32) + (1.0 - b2) * r32 * r32
        bc1 = scalars[0]
        bc2 = scalars[1]
        n_scr[...] = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + eps)
        m_out[0] = m_new.astype(m_out.dtype)
        v_out[0] = v_new.astype(v_out.dtype)

    lr_alpha = scalars[2]
    lr_wd = scalars[3]
    delta = jnp.dot(
        p_ref[0].astype(jnp.float32),
        n_scr[...],
        preferred_element_type=jnp.float32,
    )
    w_out[0] = (
        (1.0 - lr_wd) * w_ref[0].astype(jnp.float32) - lr_alpha * delta
    ).astype(w_out.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("b1", "b2", "eps", "block_d", "block_n", "interpret"),
)
def lowrank_adam_update_batched(
    w: jax.Array,  # (B, d, n)
    p: jax.Array,  # (B, d, r)
    r_g: jax.Array,  # (B, r, n)
    m: jax.Array,  # (B, r, n)
    v: jax.Array,  # (B, r, n)
    step: jax.Array,  # int32 scalar
    lr_alpha: jax.Array,  # f32 scalar
    lr_wd: jax.Array | float = 0.0,  # f32 scalar: lr * weight_decay
    *,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    block_d: int = 256,
    block_n: int = 512,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    bsz, d, r = p.shape
    assert w.shape == (bsz, d, r_g.shape[-1])
    _, rr, n = r_g.shape
    assert rr == r and m.shape == (bsz, r, n)
    bd = compat.pick_block(d, block_d)
    bn = compat.pick_block(n, block_n)
    grid = (bsz, n // bn, d // bd)

    scalars = _adam_scalars(step, lr_alpha, lr_wd, b1, b2)

    kernel = functools.partial(_adam_kernel, b1=b1, b2=b2, eps=eps)
    w_new, m_new, v_new = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bd, bn), lambda b, i, j, s: (b, j, i)),  # W
                pl.BlockSpec((1, bd, r), lambda b, i, j, s: (b, j, 0)),  # P
                pl.BlockSpec((1, r, bn), lambda b, i, j, s: (b, 0, i)),  # R
                pl.BlockSpec((1, r, bn), lambda b, i, j, s: (b, 0, i)),  # M
                pl.BlockSpec((1, r, bn), lambda b, i, j, s: (b, 0, i)),  # V
            ],
            out_specs=[
                pl.BlockSpec((1, bd, bn), lambda b, i, j, s: (b, j, i)),
                pl.BlockSpec((1, r, bn), lambda b, i, j, s: (b, 0, i)),
                pl.BlockSpec((1, r, bn), lambda b, i, j, s: (b, 0, i)),
            ],
            scratch_shapes=[pltpu.VMEM((r, bn), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(w.shape, w.dtype),
            jax.ShapeDtypeStruct(m.shape, jnp.float32),
            jax.ShapeDtypeStruct(v.shape, jnp.float32),
        ],
        compiler_params=compat.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(scalars, w, p, r_g, m, v)
    return w_new, m_new, v_new


def lowrank_adam_update(
    w: jax.Array,  # (d, n)
    p: jax.Array,  # (d, r)
    r_g: jax.Array,  # (r, n)
    m: jax.Array,  # (r, n)
    v: jax.Array,  # (r, n)
    step: jax.Array,  # int32 scalar
    lr_alpha: jax.Array,  # f32 scalar
    lr_wd: jax.Array | float = 0.0,
    *,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    block_d: int = 256,
    block_n: int = 512,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Single-matrix entry point: B == 1 batched call."""
    w_new, m_new, v_new = lowrank_adam_update_batched(
        w[None], p[None], r_g[None], m[None], v[None], step, lr_alpha, lr_wd,
        b1=b1, b2=b2, eps=eps, block_d=block_d, block_n=block_n,
        interpret=interpret,
    )
    return w_new[0], m_new[0], v_new[0]


# ---------------------------------------------------------------------------
# Momentum SGD (Theorem 3.4's optimizer; inner.msgd convention
# M' = (1-b1) M + b1 R, direction = M')
# ---------------------------------------------------------------------------


def _msgd_kernel(
    scalars,  # SMEM: (2,) f32 [lr_alpha, lr_wd]
    w_ref,  # (1, bd, bn)
    p_ref,  # (1, bd, r)
    r_ref,  # (1, r, bn)
    m_ref,  # (1, r, bn)
    w_out,  # (1, bd, bn)
    m_out,  # (1, r, bn)
    n_scr,  # VMEM scratch (r, bn) f32
    *,
    b1: float,
):
    i_d = pl.program_id(2)

    @pl.when(i_d == 0)
    def _update_moment():
        r32 = r_ref[0].astype(jnp.float32)
        m_new = (1.0 - b1) * m_ref[0].astype(jnp.float32) + b1 * r32
        n_scr[...] = m_new
        m_out[0] = m_new.astype(m_out.dtype)

    lr_alpha = scalars[0]
    lr_wd = scalars[1]
    delta = jnp.dot(
        p_ref[0].astype(jnp.float32),
        n_scr[...],
        preferred_element_type=jnp.float32,
    )
    w_out[0] = (
        (1.0 - lr_wd) * w_ref[0].astype(jnp.float32) - lr_alpha * delta
    ).astype(w_out.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("b1", "block_d", "block_n", "interpret"),
)
def lowrank_msgd_update_batched(
    w: jax.Array,  # (B, d, n)
    p: jax.Array,  # (B, d, r)
    r_g: jax.Array,  # (B, r, n)
    m: jax.Array,  # (B, r, n)
    lr_alpha: jax.Array,  # f32 scalar
    lr_wd: jax.Array | float = 0.0,
    *,
    b1: float = 0.9,
    block_d: int = 256,
    block_n: int = 512,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    bsz, d, r = p.shape
    _, rr, n = r_g.shape
    assert rr == r and w.shape == (bsz, d, n) and m.shape == (bsz, r, n)
    bd = compat.pick_block(d, block_d)
    bn = compat.pick_block(n, block_n)
    grid = (bsz, n // bn, d // bd)

    scalars = jnp.stack([
        jnp.asarray(lr_alpha, jnp.float32),
        jnp.asarray(lr_wd, jnp.float32),
    ])

    kernel = functools.partial(_msgd_kernel, b1=b1)
    w_new, m_new = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bd, bn), lambda b, i, j, s: (b, j, i)),  # W
                pl.BlockSpec((1, bd, r), lambda b, i, j, s: (b, j, 0)),  # P
                pl.BlockSpec((1, r, bn), lambda b, i, j, s: (b, 0, i)),  # R
                pl.BlockSpec((1, r, bn), lambda b, i, j, s: (b, 0, i)),  # M
            ],
            out_specs=[
                pl.BlockSpec((1, bd, bn), lambda b, i, j, s: (b, j, i)),
                pl.BlockSpec((1, r, bn), lambda b, i, j, s: (b, 0, i)),
            ],
            scratch_shapes=[pltpu.VMEM((r, bn), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(w.shape, w.dtype),
            jax.ShapeDtypeStruct(m.shape, jnp.float32),
        ],
        compiler_params=compat.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(scalars, w, p, r_g, m)
    return w_new, m_new


# ---------------------------------------------------------------------------
# Adam-mini (per-row second moment; DESIGN.md §2.8)
#
# The v statistic is one scalar per PER-LEAF row: a cross-n reduction for
# side='left' buckets, which no single (batch, n-block) grid step can see.
# It is also tiny -- (B, r) or (B, n) f32 -- so the batched entry point
# computes v' and the direction denominator with one jnp reduction over the
# R stack (one extra R read, r/d of a parameter pass) and the kernel fuses
# the rest: moment update, bias-corrected direction against the broadcast
# denominator, back-projection, W'.
# ---------------------------------------------------------------------------


def _adam_mini_kernel(
    scalars,  # SMEM: (4,) f32 [bc1, bc2 (unused), lr_alpha, lr_wd]
    w_ref,  # (1, bd, bn)
    p_ref,  # (1, bd, r)
    r_ref,  # (1, r, bn)
    m_ref,  # (1, r, bn)
    den_ref,  # (1, r, 1) side='left' | (1, 1, bn) side='right'
    w_out,  # (1, bd, bn)
    m_out,  # (1, r, bn)
    n_scr,  # VMEM scratch (r, bn) f32
    *,
    b1: float,
    side: str,
):
    i_d = pl.program_id(2)

    @pl.when(i_d == 0)
    def _update_moment():
        r32 = r_ref[0].astype(jnp.float32)
        m_new = b1 * m_ref[0].astype(jnp.float32) + (1.0 - b1) * r32
        # (r, 1) column ('left') or (1, bn) row ('right'): broadcasts
        # against the (r, bn) slab either way
        n_scr[...] = (m_new / scalars[0]) / den_ref[0]
        m_out[0] = m_new.astype(m_out.dtype)

    lr_alpha = scalars[2]
    lr_wd = scalars[3]
    delta = jnp.dot(
        p_ref[0].astype(jnp.float32),
        n_scr[...],
        preferred_element_type=jnp.float32,
    )
    w_out[0] = (
        (1.0 - lr_wd) * w_ref[0].astype(jnp.float32) - lr_alpha * delta
    ).astype(w_out.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("b1", "b2", "eps", "side", "block_d", "block_n",
                     "interpret"),
)
def lowrank_adam_mini_update_batched(
    w: jax.Array,  # (B, d, n)
    p: jax.Array,  # (B, d, r)
    r_g: jax.Array,  # (B, r, n)
    m: jax.Array,  # (B, r, n)
    v: jax.Array,  # (B, r) 'left' | (B, n) 'right'
    step: jax.Array,  # int32 scalar
    lr_alpha: jax.Array,  # f32 scalar
    lr_wd: jax.Array | float = 0.0,
    *,
    b1: float = 0.9,
    b2: float = 0.95,
    eps: float = 1e-8,
    side: str = "left",
    block_d: int = 256,
    block_n: int = 512,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    from repro.kernels.lowrank_update.ref import adam_mini_stats_ref

    bsz, d, r = p.shape
    _, rr, n = r_g.shape
    assert rr == r and w.shape == (bsz, d, n) and m.shape == (bsz, r, n)
    assert v.shape == ((bsz, r) if side == "left" else (bsz, n))
    bd = compat.pick_block(d, block_d)
    bn = compat.pick_block(n, block_n)
    grid = (bsz, n // bn, d // bd)

    v_new, denom = adam_mini_stats_ref(r_g, v, step, b2=b2, eps=eps, side=side)
    # the denominator keeps its broadcast axis: (B, r, 1) | (B, 1, n), so
    # each block's last two dims are whole or lane-aligned (Mosaic tiling)
    if side == "left":
        den_spec = pl.BlockSpec((1, r, 1), lambda b, i, j, s: (b, 0, 0))
    else:
        den_spec = pl.BlockSpec((1, 1, bn), lambda b, i, j, s: (b, 0, i))

    scalars = _adam_scalars(step, lr_alpha, lr_wd, b1, b2)

    kernel = functools.partial(_adam_mini_kernel, b1=b1, side=side)
    w_new, m_new = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bd, bn), lambda b, i, j, s: (b, j, i)),  # W
                pl.BlockSpec((1, bd, r), lambda b, i, j, s: (b, j, 0)),  # P
                pl.BlockSpec((1, r, bn), lambda b, i, j, s: (b, 0, i)),  # R
                pl.BlockSpec((1, r, bn), lambda b, i, j, s: (b, 0, i)),  # M
                den_spec,  # denom
            ],
            out_specs=[
                pl.BlockSpec((1, bd, bn), lambda b, i, j, s: (b, j, i)),
                pl.BlockSpec((1, r, bn), lambda b, i, j, s: (b, 0, i)),
            ],
            scratch_shapes=[pltpu.VMEM((r, bn), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(w.shape, w.dtype),
            jax.ShapeDtypeStruct(m.shape, jnp.float32),
        ],
        compiler_params=compat.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(scalars, w, p, r_g, m, denom)
    return w_new, m_new, v_new


# ---------------------------------------------------------------------------
# 8-bit Adam (blockwise-quantized moments; DESIGN.md §2.8)
#
# M and V live in HBM as uint8 codes (element-aligned with the canonical
# (B, r, n) stack) plus f32 per-row-chunk scales in PER-LEAF row order
# (quantize.py).  The moment phase dequantizes the (r, bn) slab in VMEM,
# updates, stashes the bias-corrected direction, and requantizes -- the
# f32 moments never touch HBM; the back-projection reads the VMEM scratch
# like the other variants.  Chunks must tile the slab: side='left' needs
# n % QBLOCK == 0 (bn is picked 256-aligned), side='right' needs
# r <= QBLOCK or r % QBLOCK == 0 (ops.py falls back to the jnp ref
# otherwise -- same math, moments round-tripping HBM as XLA temporaries).
# ---------------------------------------------------------------------------


def _dq(codes, scale, signed: bool):
    """uint8 codes -> f32 against a broadcastable scale (Mosaic casts
    uint8 to f32 only through int32)."""
    c = codes.astype(jnp.int32).astype(jnp.float32)
    if signed:
        return (c - 127.0) / 127.0 * scale
    rel = c / 255.0
    return rel * rel * scale


def _absmax_scale(x, axis: int):
    absmax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    return jnp.where(absmax > 0, absmax, 1.0)


def _q(x, scale, signed: bool):
    """f32 -> uint8 codes against a broadcastable scale (via int32)."""
    if signed:
        q = jnp.clip(jnp.round(x / scale * 127.0), -127, 127) + 127
    else:
        rel = jnp.sqrt(jnp.clip(x / scale, 0.0, 1.0))
        q = jnp.clip(jnp.round(rel * 255.0), 0, 255)
    return q.astype(jnp.int32).astype(jnp.uint8)


def _adam8bit_chunk(r32, mc, ms, vc, vs, *, axis, b1, b2, eps, bc1, bc2):
    """One quantization chunk: dequant -> Adam moments -> direction ->
    requant.  ``axis`` is the axis the chunk's scale is shared along."""
    m_new = b1 * _dq(mc, ms, True) + (1.0 - b1) * r32
    v_new = b2 * _dq(vc, vs, False) + (1.0 - b2) * r32 * r32
    n_dir = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + eps)
    ms_new = _absmax_scale(m_new, axis)
    vs_new = _absmax_scale(v_new, axis)
    return (n_dir, _q(m_new, ms_new, True), ms_new,
            _q(v_new, vs_new, False), vs_new)


def _adam8bit_kernel(
    scalars,  # SMEM: (4,) f32 [bc1, bc2, lr_alpha, lr_wd]
    w_ref,  # (1, bd, bn)
    p_ref,  # (1, bd, r)
    r_ref,  # (1, r, bn)
    mc_ref,  # (1, r, bn) uint8
    ms_ref,  # (1, r, nb) 'left' (whole row) | (1, nb_r, bn) 'right'
    vc_ref,  # (1, r, bn) uint8
    vs_ref,
    w_out,
    mc_out,
    ms_out,
    vc_out,
    vs_out,
    n_scr,  # VMEM scratch (r, bn) f32
    *,
    b1: float,
    b2: float,
    eps: float,
    side: str,
):
    i_n = pl.program_id(1)
    i_d = pl.program_id(2)
    r, bn = n_scr.shape

    @pl.when(i_d == 0)
    def _update_moments():
        kw = dict(b1=b1, b2=b2, eps=eps, bc1=scalars[0], bc2=scalars[1])
        if side == "left":
            # chunks run along n: this block holds bn // QBLOCK of them.
            # The (r, nb) scale rows stay resident for the whole batch
            # slice; a chunk's scale column is picked / written back by a
            # lane mask (exact: one term survives each sum).
            nbb = bn // QBLOCK
            lanes = jax.lax.broadcasted_iota(jnp.int32, ms_ref.shape[1:], 1)
            ms_all, vs_all = ms_ref[0], vs_ref[0]
            ms_acc, vs_acc = ms_out[0], vs_out[0]
            for c in range(nbb):
                cols = pl.ds(c * QBLOCK, QBLOCK)
                hit = lanes == i_n * nbb + c

                def pick(s_all):
                    return jnp.sum(jnp.where(hit, s_all, 0.0), axis=1,
                                   keepdims=True)

                n_dir, mc, ms, vc, vs = _adam8bit_chunk(
                    r_ref[0, :, cols].astype(jnp.float32),
                    mc_ref[0, :, cols], pick(ms_all),
                    vc_ref[0, :, cols], pick(vs_all), axis=1, **kw,
                )
                n_scr[:, cols] = n_dir
                mc_out[0, :, cols] = mc
                vc_out[0, :, cols] = vc
                ms_acc = jnp.where(hit, ms, ms_acc)
                vs_acc = jnp.where(hit, vs, vs_acc)
            ms_out[0] = ms_acc
            vs_out[0] = vs_acc
        else:
            # chunks run along r: one scale row per chunk, per column
            for k in range(ms_ref.shape[1]):
                rows = pl.ds(k * QBLOCK, min(QBLOCK, r - k * QBLOCK))
                n_dir, mc, ms, vc, vs = _adam8bit_chunk(
                    r_ref[0, rows, :].astype(jnp.float32),
                    mc_ref[0, rows, :], ms_ref[0, pl.ds(k, 1), :],
                    vc_ref[0, rows, :], vs_ref[0, pl.ds(k, 1), :],
                    axis=0, **kw,
                )
                n_scr[rows, :] = n_dir
                mc_out[0, rows, :] = mc
                vc_out[0, rows, :] = vc
                ms_out[0, pl.ds(k, 1), :] = ms
                vs_out[0, pl.ds(k, 1), :] = vs

    lr_alpha = scalars[2]
    lr_wd = scalars[3]
    delta = jnp.dot(
        p_ref[0].astype(jnp.float32),
        n_scr[...],
        preferred_element_type=jnp.float32,
    )
    w_out[0] = (
        (1.0 - lr_wd) * w_ref[0].astype(jnp.float32) - lr_alpha * delta
    ).astype(w_out.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("b1", "b2", "eps", "side", "block_d", "block_n",
                     "interpret"),
)
def lowrank_adam8bit_update_batched(
    w: jax.Array,  # (B, d, n)
    p: jax.Array,  # (B, d, r)
    r_g: jax.Array,  # (B, r, n)
    m_codes: jax.Array,  # (B, r, n) uint8
    m_scale: jax.Array,  # (B, r, n//QBLOCK) 'left' | (B, n, nb_r) 'right'
    v_codes: jax.Array,  # (B, r, n) uint8
    v_scale: jax.Array,
    step: jax.Array,  # int32 scalar
    lr_alpha: jax.Array,  # f32 scalar
    lr_wd: jax.Array | float = 0.0,
    *,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    side: str = "left",
    block_d: int = 256,
    block_n: int = 512,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    bsz, d, r = p.shape
    _, rr, n = r_g.shape
    assert rr == r and w.shape == (bsz, d, n)
    assert m_codes.shape == (bsz, r, n) and m_codes.dtype == jnp.uint8
    bd = compat.pick_block(d, block_d)
    if side == "left":
        assert n % QBLOCK == 0, "left-side 8-bit kernel needs n % 256 == 0"
        bn = compat.pick_block(n, block_n, align=QBLOCK)
        assert bn % QBLOCK == 0
        nb = n // QBLOCK
        assert m_scale.shape == (bsz, r, nb)
        # whole (r, nb) scale rows per batch slice: a (1, r, bn // QBLOCK)
        # block would not be lane-aligned.  The block is revisited across
        # the n-blocks, so that grid axis runs sequentially.
        scale_spec = pl.BlockSpec((1, r, nb), lambda b, i, j, s: (b, 0, 0))
        n_semantics = "arbitrary"
    else:
        nb_r = num_blocks(r)
        assert r <= QBLOCK or r % QBLOCK == 0, (
            "right-side 8-bit kernel needs r <= 256 or r % 256 == 0"
        )
        bn = compat.pick_block(n, block_n)
        assert m_scale.shape == (bsz, n, nb_r)
        # scales enter as (B, nb_r, n): one lane-dense row per chunk
        m_scale = jnp.swapaxes(m_scale, 1, 2)
        v_scale = jnp.swapaxes(v_scale, 1, 2)
        scale_spec = pl.BlockSpec((1, nb_r, bn), lambda b, i, j, s: (b, 0, i))
        n_semantics = "parallel"
    grid = (bsz, n // bn, d // bd)

    scalars = _adam_scalars(step, lr_alpha, lr_wd, b1, b2)

    code_spec = pl.BlockSpec((1, r, bn), lambda b, i, j, s: (b, 0, i))
    kernel = functools.partial(
        _adam8bit_kernel, b1=b1, b2=b2, eps=eps, side=side
    )
    outs = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bd, bn), lambda b, i, j, s: (b, j, i)),  # W
                pl.BlockSpec((1, bd, r), lambda b, i, j, s: (b, j, 0)),  # P
                pl.BlockSpec((1, r, bn), lambda b, i, j, s: (b, 0, i)),  # R
                code_spec,  # M codes
                scale_spec,  # M scales
                code_spec,  # V codes
                scale_spec,  # V scales
            ],
            out_specs=[
                pl.BlockSpec((1, bd, bn), lambda b, i, j, s: (b, j, i)),
                code_spec,
                scale_spec,
                code_spec,
                scale_spec,
            ],
            scratch_shapes=[pltpu.VMEM((r, bn), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(w.shape, w.dtype),
            jax.ShapeDtypeStruct(m_codes.shape, jnp.uint8),
            jax.ShapeDtypeStruct(m_scale.shape, jnp.float32),
            jax.ShapeDtypeStruct(v_codes.shape, jnp.uint8),
            jax.ShapeDtypeStruct(v_scale.shape, jnp.float32),
        ],
        compiler_params=compat.CompilerParams(
            dimension_semantics=("parallel", n_semantics, "arbitrary"),
        ),
        interpret=interpret,
    )(scalars, w, p, r_g, m_codes, m_scale, v_codes, v_scale)
    if side == "right":
        outs = (outs[0], outs[1], jnp.swapaxes(outs[2], 1, 2), outs[3],
                jnp.swapaxes(outs[4], 1, 2))
    return tuple(outs)
