"""Pallas TPU flash attention (forward), causal/windowed, GQA-aware.

Grid: (B, H, nq, nk) with the KV dimension innermost ("arbitrary" semantics:
sequential on-core so the (m, l, acc) scratch carries across KV blocks of one
query block).  The kernel runs on a heads-major (B, H, S, D) view -- the
wrapper transposes the models' (B, S, H, D) layout on the way in and out --
so every block's last two dims are (seq block, D): D (=head_dim) whole in
lanes and the q/kv block sizes as sublane multiples, which is the tiling
Mosaic requires; q_blk x D and kv_blk x D tiles feed the MXU directly.

Causal skipping: fully-masked KV blocks are skipped with ``pl.when`` (no MXU
work issued); the diagonal block applies the elementwise mask from absolute
positions (q_offset supports prefill continuation).

GQA is expressed through the K/V index_map (kv_head = q_head // group), so K/V
blocks are fetched once per query-head group rather than replicated in HBM.

Backward: registered as a custom_vjp whose backward recomputes attention via
the jnp reference (a flash-bwd kernel is future work).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import compat

NEG = -1e30


def _fwd_kernel(
    q_ref,  # (1, 1, bq, D)
    k_ref,  # (1, 1, bk, D)
    v_ref,  # (1, 1, bk, D)
    o_ref,  # (1, 1, bq, D)
    m_scr,  # (bq, 128) f32  (broadcast lanes)
    l_scr,  # (bq, 128) f32
    acc_scr,  # (bq, D) f32
    *,
    scale: float,
    causal: bool,
    window: int,
    q_offset: int,
    bq: int,
    bk: int,
    nk: int,
):
    i_q = pl.program_id(2)
    i_k = pl.program_id(3)

    @pl.when(i_k == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = i_q * bq + q_offset
    k_start = i_k * bk

    # Whole-block causal skip: block is needed iff its first kv position can
    # be visible to the last query of the block, and (for windows) its last
    # kv position is within the window of the first query... conservatively:
    needed = True
    if causal:
        needed = k_start <= q_start + bq - 1
    if window:
        needed = jnp.logical_and(needed, k_start + bk - 1 > q_start - window)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (bq, bk)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        allow = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            allow = jnp.logical_and(allow, kpos <= qpos)
        if window:
            allow = jnp.logical_and(allow, kpos > qpos - window)
        s = jnp.where(allow, s, NEG)
        m_prev = m_scr[:, :1]  # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)  # (bq, 1)
        l_new = corr * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_scr[...] = corr * acc_scr[...] + pv
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(i_k == nk - 1)
    def _finalize():
        l = l_scr[:, :1]
        out = acc_scr[...] / jnp.maximum(l, 1e-30)
        o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "window", "q_offset", "block_q", "block_kv", "interpret"
    ),
)
def flash_attention_fwd(
    q: jax.Array,  # (B, Sq, H, D)
    k: jax.Array,  # (B, Sk, KVH, D)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    block_q: int = 256,
    block_kv: int = 256,
    interpret: bool = False,
) -> jax.Array:
    b, sq, h, d = q.shape
    _, sk, kvh, _ = k.shape
    g = h // kvh
    bq = min(block_q, sq)
    bk = min(block_kv, sk)
    if sq % bq or sk % bk:
        bq, bk = sq, sk  # ragged test shapes: single block
    nq, nk = sq // bq, sk // bk
    scale = 1.0 / (d**0.5)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, window=window,
        q_offset=q_offset, bq=bq, bk=bk, nk=nk,
    )
    heads_major = lambda x: jnp.swapaxes(x, 1, 2)  # (B, S, H, D) <-> (B, H, S, D)
    out = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec(
                (1, 1, bk, d), lambda ib, ih, iq, ik: (ib, ih // g, ik, 0)
            ),
            pl.BlockSpec(
                (1, 1, bk, d), lambda ib, ih, iq, ik: (ib, ih // g, ik, 0)
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, bq, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=compat.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(heads_major(q), heads_major(k), heads_major(v))
    return heads_major(out)


# ---------------------------------------------------------------------------
# custom_vjp wrapper: pallas forward, reference-recompute backward
# ---------------------------------------------------------------------------


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6)
)
def flash_attention(
    q, k, v, causal: bool = True, window: int = 0, q_offset: int = 0,
    interpret: bool = False,
):
    return flash_attention_fwd(
        q, k, v, causal=causal, window=window, q_offset=q_offset,
        interpret=interpret,
    )


def _fa_fwd(q, k, v, causal, window, q_offset, interpret):
    out = flash_attention_fwd(
        q, k, v, causal=causal, window=window, q_offset=q_offset,
        interpret=interpret,
    )
    return out, (q, k, v)


def _fa_bwd(causal, window, q_offset, interpret, res, g):
    from repro.kernels.flash_attention.ref import flash_attention_ref

    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: flash_attention_ref(
            q_, k_, v_, causal=causal, window=window, q_offset=q_offset
        ),
        q, k, v,
    )
    return vjp(g)


flash_attention.defvjp(_fa_fwd, _fa_bwd)
