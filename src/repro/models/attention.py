"""Attention implementations.

Three interchangeable implementations behind one signature (all exact math,
different memory/FLOP envelopes):

  * ``exact``   -- materializes (B, H, Sq, Sk) logits.  Right for short
    sequences, decode (Sq=1), and as the test oracle.
  * ``chunked`` -- flash-style two-level scan with online softmax, O(Cq*Ck)
    transient memory.  Required for the 32k prefill shapes.  Causal block
    skipping is done with a ``lax.cond`` on the block index, so fully-masked
    KV blocks cost no FLOPs at runtime (the dry-run HLO still *contains* the
    branch; see EXPERIMENTS.md §Perf for the measured effect).
  * ``pallas``  -- the TPU flash-attention kernel in repro/kernels (dispatch
    falls back to ``chunked`` on non-TPU backends).

GQA layout: q (B, Sq, H, D), k/v (B, Sk, KVH, D) with H = G * KVH.
Masking is positional: ``q_positions`` (B, Sq) and ``kv_positions`` (B, Sk)
carry *absolute* token positions; causal = kv_pos <= q_pos; a sliding window
additionally requires kv_pos > q_pos - window; negative kv_pos marks invalid
(unwritten) cache slots.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

_NEG = -1e30


def _mask(
    q_pos: jax.Array,  # (B, Sq)
    kv_pos: jax.Array,  # (B, Sk)
    causal: bool,
    window: int,
) -> jax.Array:
    """(B, Sq, Sk) boolean allow-mask."""
    qp = q_pos[:, :, None]
    kp = kv_pos[:, None, :]
    m = kp >= 0
    if causal:
        m = m & (kp <= qp)
    if window and window > 0:
        m = m & (kp > qp - window)
    return m


def exact_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_positions: jax.Array,
    kv_positions: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
) -> jax.Array:
    b, sq, h, d = q.shape
    _, sk, kvh, _ = k.shape
    g = h // kvh
    scale = 1.0 / (d**0.5)
    qg = q.reshape(b, sq, kvh, g, d)
    logits = jnp.einsum(
        "bqkgd,bskd->bkgqs", qg, k, preferred_element_type=jnp.float32
    ) * scale
    allow = _mask(q_positions, kv_positions, causal, window)
    logits = jnp.where(allow[:, None, None, :, :], logits, _NEG)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum(
        "bkgqs,bskd->bqkgd", probs.astype(v.dtype), v
    )
    return out.reshape(b, sq, h, d)


def chunked_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_positions: jax.Array,
    kv_positions: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    chunk_q: int = 512,
    chunk_kv: int = 1024,
    skip_masked_blocks: bool = True,
) -> jax.Array:
    """Flash-style exact attention with O(chunk^2) transient memory."""
    b, sq, h, d = q.shape
    _, sk, kvh, _ = k.shape
    g = h // kvh
    scale = 1.0 / (d**0.5)

    cq = min(chunk_q, sq)
    ck = min(chunk_kv, sk)
    pad_q = (-sq) % cq
    pad_k = (-sk) % ck
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    qpos = jnp.pad(q_positions, ((0, 0), (0, pad_q)), constant_values=-1)
    kp = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    kpos = jnp.pad(kv_positions, ((0, 0), (0, pad_k)), constant_values=-1)
    nq = qp.shape[1] // cq
    nk = kp.shape[1] // ck

    # (nq, B, Cq, ...) query blocks; (nk, B, Ck, ...) kv blocks.
    qb = qp.reshape(b, nq, cq, kvh, g, d).transpose(1, 0, 2, 3, 4, 5)
    qposb = qpos.reshape(b, nq, cq).transpose(1, 0, 2)
    kb = kp.reshape(b, nk, ck, kvh, d).transpose(1, 0, 2, 3, 4)
    vb = vp.reshape(b, nk, ck, kvh, d).transpose(1, 0, 2, 3, 4)
    kposb = kpos.reshape(b, nk, ck).transpose(1, 0, 2)

    @jax.checkpoint  # flash-style bwd: per-q-block recompute; without this
    def q_block(carry, xs):  # the outer scan stores every (m,l,acc) carry
        del carry
        qi, qpi = xs  # (B,Cq,KVH,G,D), (B,Cq)

        @jax.checkpoint  # inner: recompute block logits instead of storing
        def kv_block(inner, xs_kv):  # (B,H,Cq,Ck) probabilities per iteration
            m_run, l_run, acc = inner
            ki, vi, kpi = xs_kv

            def compute(operands):
                m_run, l_run, acc, ki, vi, kpi = operands
                logits = jnp.einsum(
                    "bqkgd,bskd->bkgqs", qi, ki,
                    preferred_element_type=jnp.float32,
                ) * scale
                allow = _mask(qpi, kpi, causal, window)
                logits = jnp.where(allow[:, None, None, :, :], logits, _NEG)
                m_new = jnp.maximum(m_run, jnp.max(logits, axis=-1))
                p = jnp.exp(logits - m_new[..., None])
                corr = jnp.exp(m_run - m_new)
                l_new = corr * l_run + jnp.sum(p, axis=-1)
                pv = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(vi.dtype), vi)
                acc_new = corr[..., None] * acc + pv.astype(jnp.float32)
                return m_new, l_new, acc_new

            if skip_masked_blocks and causal and not window:
                # Whole-block causal skip: if every kv position in the block
                # exceeds every query position, the block contributes nothing.
                # lax.cond => no FLOPs at runtime for skipped blocks.
                blk_min_kv = jnp.min(jnp.where(kpi >= 0, kpi, 2**30))
                blk_max_q = jnp.max(qpi)
                needed = blk_min_kv <= blk_max_q
                m_run, l_run, acc = jax.lax.cond(
                    needed,
                    compute,
                    lambda ops: (ops[0], ops[1], ops[2]),
                    (m_run, l_run, acc, ki, vi, kpi),
                )
            else:
                m_run, l_run, acc = compute((m_run, l_run, acc, ki, vi, kpi))
            return (m_run, l_run, acc), None

        m0 = jnp.full((b, kvh, g, cq), _NEG, jnp.float32)
        l0 = jnp.zeros((b, kvh, g, cq), jnp.float32)
        a0 = jnp.zeros((b, kvh, g, cq, d), jnp.float32)
        (m_f, l_f, acc), _ = jax.lax.scan(
            kv_block, (m0, l0, a0), (kb, vb, kposb)
        )
        out = acc / jnp.maximum(l_f, 1e-30)[..., None]
        return None, out.astype(q.dtype)

    _, outs = jax.lax.scan(q_block, None, (qb, qposb))
    # (nq, B, KVH, G, Cq, D) -> (B, nq, Cq, KVH, G, D) -> (B, S, H, D)
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(b, nq * cq, h, d)
    return out[:, :sq]


def paged_decode_attention(
    q: jax.Array,  # (B, 1, H, D) -- one query per decode slot
    pages_k: jax.Array,  # (P, ps, KVH, D) shared page pool
    pages_v: jax.Array,
    page_table: jax.Array,  # (B, MP) int32, -1 = unallocated
    seq_lens: jax.Array,  # (B,) int32, incl. the token being decoded
    *,
    window: int = 0,
    impl: str = "auto",
) -> jax.Array:
    """Decode-shaped dispatch: K/V read through the page table.

    q_len must be 1 (the decode contract -- the kernel grid has no query
    dimension); ``impl='pallas'`` (or ``'auto'`` on a TPU backend) routes to
    the ``kernels/flash_attention_decode`` Pallas kernel, which streams one
    pool page per grid step through VMEM; everything else -- CPU backends,
    off-alignment page sizes / head dims -- takes the jnp reference that
    materializes the gathered K/V (the ops-layer gate decides).  Causality
    is structural (see ref.py), so there is no ``causal`` switch.
    """
    if q.shape[1] != 1:
        raise ValueError(
            f"paged_decode_attention requires q_len=1, got {q.shape[1]}"
        )
    from repro.kernels.flash_attention_decode import ops as fad_ops

    if impl in ("auto", "pallas"):
        return fad_ops.paged_decode_attention(
            q, pages_k, pages_v, page_table, seq_lens, window=window
        )
    from repro.kernels.flash_attention_decode.ref import (
        paged_decode_attention_ref,
    )

    return paged_decode_attention_ref(
        q, pages_k, pages_v, page_table, seq_lens, window=window
    )


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_positions: jax.Array,
    kv_positions: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    impl: str = "auto",
    chunk_q: int = 512,
    chunk_kv: int = 1024,
) -> jax.Array:
    """Implementation dispatch.  ``auto``: exact for small/decode; for long
    sequences the Pallas flash kernel on TPU backends (self-attention, where
    the kernel's contiguous positions hold), chunked jnp otherwise."""
    sq, sk = q.shape[1], k.shape[1]
    if impl == "auto":
        # Exact materializes (B,H,Sq,Sk) logits -- only affordable for small
        # products and single-query decode (the 2048^2 threshold is mirrored
        # in roofline/analysis.py EXACT_ATTN_MAX_ELEMS).
        if sq == 1 or (sq * sk) <= 2048 * 2048:
            impl = "exact"
        elif sq == sk and jax.default_backend() == "tpu":
            impl = "pallas"
        else:
            impl = "chunked"
    if impl == "pallas":
        from repro.kernels.flash_attention import ops as fa_ops

        return fa_ops.flash_attention(
            q, k, v, q_positions, kv_positions, causal=causal, window=window
        )
    if impl == "exact":
        return exact_attention(
            q, k, v, q_positions, kv_positions, causal=causal, window=window
        )
    if impl == "chunked":
        return chunked_attention(
            q, k, v, q_positions, kv_positions,
            causal=causal, window=window, chunk_q=chunk_q, chunk_kv=chunk_kv,
        )
    raise ValueError(f"unknown attention impl {impl!r}")
