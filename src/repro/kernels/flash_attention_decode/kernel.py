"""Pallas TPU paged decode attention (q_len = 1), GQA-aware.

Grid: (B, H, MP) -- one program per (decode slot, query head, kv page), the
page dimension innermost with "arbitrary" semantics so the (m, l, acc)
online-softmax scratch carries across the pages of one slot sequentially
on-core.

The page table is a scalar-prefetch operand (``PrefetchScalarGridSpec``): the
K/V index maps read ``page_table[b, ik]`` to pick which pool page the next
grid step DMAs into VMEM, so K/V arrive page-by-page straight from the pool
-- the gathered (B, MP*ps, KVH, D) intermediate the jnp reference
materializes never exists.  Unallocated table entries (-1) are clamped to
page 0 for the DMA and contribute nothing: pages at or past
``ceil(seq_len/ps)`` are skipped with ``pl.when`` before any MXU work.

Masking is structural: the query sits at position ``seq_len - 1`` (its K/V
is written to the pool before the kernel runs, mirroring the ring-buffer
decode paths), so causality is ``kv_pos < seq_len`` plus the optional
sliding window.  Empty slots (``seq_len == 0``) produce zeros, not NaN.

GQA is expressed through the K/V index maps (kv head = q head // group),
matching the training kernel in ``kernels/flash_attention``.

The kernel reads the pool as (P, ps, KVH*D) and the query / output as
(B, 1, H*D) -- free reshapes of the (.., KVH, D) / (.., H, D) layouts that
merge the head and head-dim axes -- so each block's last two dims are
(ps, D) or (1, D) with D lane-aligned, the tiling Mosaic requires; a head
is a D-wide lane block.

VMEM budget per program: one (ps, D) K tile + one (ps, D) V tile + the
(1, 128)/(1, D) f32 scratch -- a few KB at ps=16..64, far below the ~16 MB
core budget, leaving the pipeline free to double-buffer page DMAs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import compat

NEG = -1e30


def _decode_kernel(
    pt_ref,  # (B*MP,) int32 scalar-prefetch page table (flattened)
    sl_ref,  # (B,) int32 scalar-prefetch seq lens
    q_ref,  # (1, 1, D)
    k_ref,  # (1, ps, D)
    v_ref,  # (1, ps, D)
    o_ref,  # (1, 1, D)
    m_scr,  # (1, 128) f32
    l_scr,  # (1, 128) f32
    acc_scr,  # (1, D) f32
    *,
    scale: float,
    window: int,
    ps: int,
    mp: int,
):
    i_b = pl.program_id(0)
    i_k = pl.program_id(2)
    seq_len = sl_ref[i_b]

    @pl.when(i_k == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    k_start = i_k * ps
    needed = k_start < seq_len
    if window:
        needed = jnp.logical_and(needed, k_start + ps > seq_len - window)

    @pl.when(needed)
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # (1, D)
        k = k_ref[0].astype(jnp.float32)  # (ps, D)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (1, ps)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)
        allow = kpos < seq_len
        if window:
            allow = jnp.logical_and(allow, kpos > seq_len - 1 - window)
        s = jnp.where(allow, s, NEG)
        m_prev = m_scr[:, :1]  # (1, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.where(allow, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_new = corr * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (1, D)
        acc_scr[...] = corr * acc_scr[...] + pv
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(i_k == mp - 1)
    def _finalize():
        l = l_scr[:, :1]
        out = acc_scr[...] / jnp.maximum(l, 1e-30)
        o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "interpret")
)
def paged_decode_attention_kernel(
    q: jax.Array,  # (B, 1, H, D)
    pages_k: jax.Array,  # (P, ps, KVH, D)
    pages_v: jax.Array,
    page_table: jax.Array,  # (B, MP) int32
    seq_lens: jax.Array,  # (B,) int32
    *,
    window: int = 0,
    interpret: bool = False,
) -> jax.Array:
    b, sq, h, d = q.shape
    if sq != 1:
        raise ValueError(f"paged decode attention requires q_len=1, got {sq}")
    p, ps, kvh, _ = pages_k.shape
    mp = page_table.shape[1]
    g = h // kvh
    scale = 1.0 / (d**0.5)

    kernel = functools.partial(
        _decode_kernel, scale=scale, window=window, ps=ps, mp=mp,
    )
    # K/V index maps read the prefetched page table: grid step (b, h, ik)
    # DMAs pool page page_table[b, ik] (clamped; -1 entries are skipped by
    # the seq_len guard before any compute).
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, h, mp),
        in_specs=[
            pl.BlockSpec((1, 1, d), lambda ib, ih, ik, pt, sl: (ib, 0, ih)),
            pl.BlockSpec(
                (1, ps, d),
                lambda ib, ih, ik, pt, sl: (
                    jnp.maximum(pt[ib * mp + ik], 0), 0, ih // g
                ),
            ),
            pl.BlockSpec(
                (1, ps, d),
                lambda ib, ih, ik, pt, sl: (
                    jnp.maximum(pt[ib * mp + ik], 0), 0, ih // g
                ),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, d), lambda ib, ih, ik, pt, sl: (ib, 0, ih)
        ),
        scratch_shapes=[
            pltpu.VMEM((1, 128), jnp.float32),
            pltpu.VMEM((1, 128), jnp.float32),
            pltpu.VMEM((1, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, h * d), q.dtype),
        compiler_params=compat.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(
        page_table.reshape(-1).astype(jnp.int32),
        seq_lens.astype(jnp.int32),
        q.reshape(b, 1, h * d),
        pages_k.reshape(p, ps, kvh * d),
        pages_v.reshape(p, ps, kvh * d),
    )
    return out.reshape(b, 1, h, d)
