"""Shared neural-net layers (pure functions over explicit param dicts).

No flax/haiku -- parameters are plain pytrees created by ``init_*`` helpers
and consumed by pure ``apply``-style functions, so the optimizer, sharding
rules, and checkpointing all see one uniform representation.

Naming matters: the sharding rules (launch/sharding.py) and the low-rank
filter (core/lowrank.py DEFAULT_EXCLUDE) pattern-match parameter path names.
Conventions:  *_proj = 2-D projection matrices (low-rank eligible);
``embed``/``lm_head``/``norm``/``bias``/``router``/``conv``/``a_log``/``dt_*``
are excluded from low-rank projection per GaLore practice.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.rmsnorm import ops as rmsnorm_ops

PyTree = Any

# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------


def dense_init(key, m: int, n: int, scale: Optional[float] = None,
               dtype=jnp.float32) -> jax.Array:
    """Truncated-normal fan-in init (LLaMA-style)."""
    if scale is None:
        scale = 1.0 / math.sqrt(m)
    return (jax.random.truncated_normal(key, -3.0, 3.0, (m, n), jnp.float32)
            * scale).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype=jnp.float32) -> jax.Array:
    return (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    # Backend-dispatched like every other kernel family: the fused Pallas
    # kernel on TPU (one read + one write per row block), the jnp ref
    # elsewhere (kernels/rmsnorm/ops.py) -- identical numerics.
    return rmsnorm_ops.rmsnorm(x, scale, eps)


def layernorm(x: jax.Array, scale: jax.Array, bias: jax.Array,
              eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    """(head_dim//2,) inverse frequencies."""
    return 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding.  x: (B, S, H, D), positions: (B, S) int32."""
    d = x.shape[-1]
    inv = rope_frequencies(d, theta)  # (D/2,)
    ang = positions.astype(jnp.float32)[..., None] * inv  # (B, S, D/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP variants
# ---------------------------------------------------------------------------


def init_mlp(key, cfg: ModelConfig, d_ff: Optional[int] = None) -> PyTree:
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    dt = cfg.param_dtype
    ks = jax.random.split(key, 3)
    out_scale = 1.0 / math.sqrt(ff * 2 * cfg.n_layers)
    if cfg.mlp_kind == "swiglu":
        return {
            "gate_proj": dense_init(ks[0], d, ff, dtype=dt),
            "up_proj": dense_init(ks[1], d, ff, dtype=dt),
            "down_proj": dense_init(ks[2], ff, d, scale=out_scale, dtype=dt),
        }
    if cfg.mlp_kind == "squared_relu":
        return {
            "up_proj": dense_init(ks[1], d, ff, dtype=dt),
            "down_proj": dense_init(ks[2], ff, d, scale=out_scale, dtype=dt),
        }
    raise ValueError(f"unknown mlp_kind {cfg.mlp_kind}")


def apply_mlp(params: PyTree, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    dt = x.dtype
    if cfg.mlp_kind == "swiglu":
        g = x @ params["gate_proj"].astype(dt)
        u = x @ params["up_proj"].astype(dt)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(dt) * u
        return h @ params["down_proj"].astype(dt)
    # nemotron-4: squared ReLU, no gate
    u = x @ params["up_proj"].astype(dt)
    h = jnp.square(jax.nn.relu(u.astype(jnp.float32))).astype(dt)
    return h @ params["down_proj"].astype(dt)


# ---------------------------------------------------------------------------
# Chunked cross entropy (memory-efficient loss for huge vocab x long seq)
# ---------------------------------------------------------------------------


def chunked_cross_entropy(
    hidden: jax.Array,  # (B, S, D)
    lm_head: jax.Array,  # (D, V)
    labels: jax.Array,  # (B, S) int32; -1 = masked
    chunk: int = 2048,
) -> Tuple[jax.Array, jax.Array]:
    """Mean NLL over non-masked tokens without materializing (B, S, V).

    Scans over SEQUENCE chunks -- the batch dim is preserved (never flattened
    into the sequence), so the data-parallel sharding of ``hidden`` survives
    and per-chunk logits stay sharded (B/dp, chunk, V/tp).  The chunk body is
    rematerialized: backward recomputes chunk logits instead of storing
    O(S x V) residuals.  Returns (mean_loss, n_tokens).
    """
    b, s, d = hidden.shape
    cs = min(chunk, s)
    pad = (-s) % cs
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    nh = hidden.shape[1] // cs
    hs = hidden.reshape(b, nh, cs, d).transpose(1, 0, 2, 3)  # (nh,B,cs,D)
    ys = labels.reshape(b, nh, cs).transpose(1, 0, 2)

    @jax.checkpoint
    def body(carry, xs):
        total, count = carry
        hc, yc = xs  # (B, cs, D), (B, cs)
        logits = (hc @ lm_head.astype(hc.dtype)).astype(jnp.float32)
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        yc_safe = jnp.maximum(yc, 0)
        picked = jnp.take_along_axis(
            logits, yc_safe[..., None], axis=-1
        )[..., 0]
        mask = (yc >= 0).astype(jnp.float32)
        nll = (logz - picked) * mask
        return (total + jnp.sum(nll), count + jnp.sum(mask)), None

    (total, count), _ = jax.lax.scan(body, (jnp.zeros(()), jnp.zeros(())),
                                     (hs, ys))
    return total / jnp.maximum(count, 1.0), count


# ---------------------------------------------------------------------------
# Sharding-constraint helper (activation annotations)
# ---------------------------------------------------------------------------


def manual_axis_names(mesh) -> set:
    """Axes of ``mesh`` -- the abstract mesh of the current trace, from
    ``jax.sharding.get_abstract_mesh()`` -- whose type is ``Manual``: we
    are inside a ``shard_map`` over them.  Placement over a manual axis is
    pinned by the enclosing ``shard_map``, and a sharding constraint may
    not name it."""
    from jax.sharding import AxisType

    return {
        name for name, kind in zip(mesh.axis_names, mesh.axis_types)
        if kind == AxisType.Manual
    }


def shard_activations(x: jax.Array, cfg=None) -> jax.Array:
    """Annotate activation sharding at block boundaries (no-op off-mesh).

    Batch dim -> DP axes always.  With ``cfg.seq_shard_activations``
    (sequence parallelism), dim 1 (sequence) is additionally sharded over
    ``model`` -- the remat-saved layer-boundary activations then cost 1/TP
    the memory, at the price of per-layer all-gathers entering attention
    (the Megatron-SP trade; measured in EXPERIMENTS.md §Perf).

    Axes that are manual in the current trace context are skipped: inside
    a shard_map region only the still-auto axes can be constrained.
    """
    from jax.sharding import PartitionSpec as P

    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1:
        return x
    manual = manual_axis_names(mesh)
    axes = [
        n for n in ("pod", "data")
        if n in mesh.axis_names and n not in manual
    ]
    if not axes:
        return x
    batch_axes = tuple(axes) if len(axes) > 1 else axes[0]
    total = 1
    for a in axes:
        total *= mesh.shape[a]
    spec = [None] * x.ndim
    if x.shape[0] % total == 0:
        spec[0] = batch_axes
    if (
        cfg is not None
        and getattr(cfg, "seq_shard_activations", False)
        and x.ndim >= 3
        and "model" in mesh.axis_names
        and "model" not in manual
        and x.shape[1] % mesh.shape["model"] == 0
        and x.shape[1] >= 2 * mesh.shape["model"]
    ):
        spec[1] = "model"
    if all(s is None for s in spec):
        return x
    return jax.lax.with_sharding_constraint(x, P(*spec))
