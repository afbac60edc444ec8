"""Mamba-2 (SSD: state-space duality) blocks -- `mamba2-370m`, and the SSM
half of `hymba-1.5b`.

Chunked SSD algorithm (Dao & Gu 2024), TPU-adapted:
  * the sequence is split into chunks of ``cfg.ssm_chunk``;
  * within a chunk the output is a small quadratic (attention-like) einsum --
    MXU-friendly dense GEMMs;
  * across chunks a single (head_dim x d_state) state per head is carried by
    ``lax.scan`` (sequential in chunk count, parallel in batch/heads).

Decode is the O(1) recurrent form: h = a*h + dt*(B (x) x); y = C.h + D*x,
with a depthwise-conv ring buffer for the conv4 frontend.

Parameter naming: ``*_proj`` matrices are low-rank-optimizer eligible;
``a_log``, ``dt_bias``, ``d_skip``, ``conv_*``, ``norm*`` are excluded
(1-D / recurrence-critical; GaLore convention).
"""
from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import transformer as tfm

PyTree = Any

_CONV_K = 4  # depthwise causal conv width (mamba2 default)


def ssm_dims(cfg: ModelConfig) -> Dict[str, int]:
    d_inner = cfg.ssm_expand * cfg.d_model
    n_heads = d_inner // cfg.ssm_head_dim
    n = cfg.ssm_state
    conv_dim = d_inner + 2 * n  # x, B, C share the conv (n_groups = 1)
    return dict(d_inner=d_inner, n_heads=n_heads, n=n, conv_dim=conv_dim,
                p=cfg.ssm_head_dim)


def init_ssm_mixer(key: jax.Array, cfg: ModelConfig) -> PyTree:
    dims = ssm_dims(cfg)
    d, d_inner, n, h = cfg.d_model, dims["d_inner"], dims["n"], dims["n_heads"]
    dt_proj_dim = h
    in_dim = 2 * d_inner + 2 * n + dt_proj_dim  # z, x, B, C, dt
    dt = cfg.param_dtype
    ks = jax.random.split(key, 4)
    out_scale = 1.0 / math.sqrt(d_inner * 2 * cfg.n_layers)
    # dt bias init so softplus(dt_bias) spans [1e-3, 1e-1] (mamba default).
    u = jax.random.uniform(ks[2], (h,), jnp.float32)
    dt_init = jnp.exp(
        u * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3)
    )
    dt_bias = dt_init + jnp.log(-jnp.expm1(-dt_init))  # inv softplus
    return {
        "in_proj": L.dense_init(ks[0], d, in_dim, dtype=dt),
        "out_proj": L.dense_init(ks[1], d_inner, d, scale=out_scale, dtype=dt),
        "conv_w": (jax.random.normal(ks[3], (_CONV_K, dims["conv_dim"]),
                                     jnp.float32) * 0.02).astype(dt),
        "conv_b": jnp.zeros((dims["conv_dim"],), dt),
        "a_log": jnp.log(jnp.arange(1, h + 1, dtype=jnp.float32)),
        "dt_bias": dt_bias.astype(jnp.float32),
        "d_skip": jnp.ones((h,), jnp.float32),
        "ssm_norm_scale": jnp.ones((d_inner,), dt),
    }


def _split_in_proj(zxbcdt: jax.Array, cfg: ModelConfig):
    dims = ssm_dims(cfg)
    d_inner, n, h = dims["d_inner"], dims["n"], dims["n_heads"]
    z, x, b_mat, c_mat, dt = jnp.split(
        zxbcdt,
        [d_inner, 2 * d_inner, 2 * d_inner + n, 2 * d_inner + 2 * n],
        axis=-1,
    )
    return z, x, b_mat, c_mat, dt


def _causal_conv(xbc: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv over (B, S, C) with kernel (K, C)."""
    k = w.shape[0]
    pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    out = sum(
        pad[:, i : i + xbc.shape[1], :] * w[i][None, None, :]
        for i in range(k)
    )
    return jax.nn.silu((out + b[None, None, :]).astype(jnp.float32)).astype(
        xbc.dtype
    )


def ssd_chunked(
    x: jax.Array,  # (B, S, H, P)
    dt: jax.Array,  # (B, S, H) post-softplus
    a: jax.Array,  # (H,) negative decay rates
    b_mat: jax.Array,  # (B, S, N)
    c_mat: jax.Array,  # (B, S, N)
    chunk: int,
    init_state: Optional[jax.Array] = None,  # (B, H, N, P)
) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD scan.  Returns (y (B,S,H,P), final_state (B,H,N,P))."""
    bsz, s, h, p = x.shape
    n = b_mat.shape[-1]
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b_mat = jnp.pad(b_mat, ((0, 0), (0, pad), (0, 0)))
        c_mat = jnp.pad(c_mat, ((0, 0), (0, pad), (0, 0)))
    nc = x.shape[1] // chunk
    # chunked views: (NC, B, Q, ...)
    xq = x.reshape(bsz, nc, chunk, h, p).transpose(1, 0, 2, 3, 4)
    dtq = dt.reshape(bsz, nc, chunk, h).transpose(1, 0, 2, 3)
    bq = b_mat.reshape(bsz, nc, chunk, n).transpose(1, 0, 2, 3)
    cq = c_mat.reshape(bsz, nc, chunk, n).transpose(1, 0, 2, 3)

    if init_state is None:
        init_state = jnp.zeros((bsz, h, n, p), jnp.float32)

    @jax.checkpoint  # recompute intra-chunk (B,Q,Q,H) factors in bwd
    def body(state, xs):
        xc, dtc, bc, cc = xs  # (B,Q,H,P), (B,Q,H), (B,Q,N), (B,Q,N)
        dtc32 = dtc.astype(jnp.float32)
        la = dtc32 * a[None, None, :]  # log decay per step (B,Q,H), <= 0
        cum = jnp.cumsum(la, axis=1)  # (B,Q,H)
        # intra-chunk: Lmat[b,h,i,j] = exp(cum_i - cum_j) for i >= j
        diff = cum[:, :, None, :] - cum[:, None, :, :]  # (B,Qi,Qj,H)
        mask = jnp.tril(jnp.ones((chunk, chunk), bool))
        lmat = jnp.where(mask[None, :, :, None], jnp.exp(diff), 0.0)
        cb = jnp.einsum("bin,bjn->bij", cc.astype(jnp.float32),
                        bc.astype(jnp.float32))  # (B,Qi,Qj)
        w = cb[:, :, :, None] * lmat  # (B,Qi,Qj,H)
        xdt = xc.astype(jnp.float32) * dtc32[..., None]  # (B,Q,H,P)
        y_diag = jnp.einsum("bijh,bjhp->bihp", w, xdt)
        # inter-chunk contribution from the carried state
        decay_in = jnp.exp(cum)  # decay from chunk start to pos i
        y_off = jnp.einsum(
            "bin,bhnp->bihp", cc.astype(jnp.float32), state
        ) * decay_in[..., None]
        # new chunk state
        decay_out = jnp.exp(cum[:, -1:, :] - cum)  # (B,Q,H)
        sbar = jnp.einsum(
            "bjn,bjhp->bhnp", bc.astype(jnp.float32),
            xdt * decay_out[..., None],
        )
        chunk_decay = jnp.exp(cum[:, -1, :])  # (B,H)
        state = state * chunk_decay[:, :, None, None] + sbar
        return state, (y_diag + y_off)

    final_state, ys = jax.lax.scan(body, init_state, (xq, dtq, bq, cq))
    y = ys.transpose(1, 0, 2, 3, 4).reshape(bsz, nc * chunk, h, p)
    return y[:, :s].astype(x.dtype), final_state


def _shard_ssm_heads(x: jax.Array, cfg: ModelConfig, head_axis: int):
    """Head-parallel SSD (perf iteration): shard the H dim over `model`.

    The natural SSM tensor parallelism -- every SSD einsum is head-parallel,
    so sharding H keeps all chunk math local and moves the layer's collective
    to the single out_proj psum (like a Megatron MLP)."""
    if not cfg.ssm_head_tp:
        return x
    from jax.sharding import PartitionSpec as P

    from repro.models.layers import manual_axis_names

    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or "model" not in mesh.axis_names:
        return x
    manual = manual_axis_names(mesh)
    if "model" in manual:
        return x
    n = mesh.shape["model"]
    if x.shape[head_axis] % n != 0:
        return x
    spec = [None] * x.ndim
    spec[head_axis] = "model"
    dp = [a for a in ("pod", "data")
          if a in mesh.axis_names and a not in manual]
    total = 1
    for a in dp:
        total *= mesh.shape[a]
    if dp and x.shape[0] % total == 0 and x.shape[0] >= total:
        spec[0] = tuple(dp) if len(dp) > 1 else dp[0]
    return jax.lax.with_sharding_constraint(x, P(*spec))


def apply_ssm_mixer(
    p: PyTree,
    u: jax.Array,  # (B, S, D) normed input
    cfg: ModelConfig,
    *,
    init_state: Optional[jax.Array] = None,
    return_state: bool = False,
):
    dims = ssm_dims(cfg)
    h, pdim, n = dims["n_heads"], dims["p"], dims["n"]
    dt_ = u.dtype
    zxbcdt = u @ p["in_proj"].astype(dt_)
    z, x, b_mat, c_mat, dt_raw = _split_in_proj(zxbcdt, cfg)
    xbc = jnp.concatenate([x, b_mat, c_mat], axis=-1)
    xbc = _causal_conv(xbc, p["conv_w"].astype(dt_), p["conv_b"].astype(dt_))
    x, b_mat, c_mat = jnp.split(
        xbc, [dims["d_inner"], dims["d_inner"] + n], axis=-1
    )
    bsz, s, _ = x.shape
    xh = x.reshape(bsz, s, h, pdim)
    xh = _shard_ssm_heads(xh, cfg, 2)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    dt = _shard_ssm_heads(dt, cfg, 2)
    a = -jnp.exp(p["a_log"])  # (H,) negative
    y, state = ssd_chunked(xh, dt, a, b_mat, c_mat, cfg.ssm_chunk,
                           init_state=init_state)
    y = _shard_ssm_heads(y, cfg, 2)
    y = y + xh.astype(jnp.float32).astype(dt_) * p["d_skip"].astype(dt_)[
        None, None, :, None
    ]
    y = y.reshape(bsz, s, dims["d_inner"])
    y = y * jax.nn.silu(z.astype(jnp.float32)).astype(dt_)
    y = L.rmsnorm(y, p["ssm_norm_scale"], cfg.rms_eps)
    out = y @ p["out_proj"].astype(dt_)
    if return_state:
        return out, state
    return out


# ---------------------------------------------------------------------------
# Recurrent decode (O(1) per token)
# ---------------------------------------------------------------------------


class SSMLayerCache(NamedTuple):
    conv: jax.Array  # (B, K-1, conv_dim) last inputs to the causal conv
    state: jax.Array  # (B, H, N, P) f32


def init_layer_cache(cfg: ModelConfig, batch: int) -> SSMLayerCache:
    dims = ssm_dims(cfg)
    return SSMLayerCache(
        conv=jnp.zeros((batch, _CONV_K - 1, dims["conv_dim"]), cfg.dtype),
        state=jnp.zeros(
            (batch, dims["n_heads"], dims["n"], dims["p"]), jnp.float32
        ),
    )


def decode_ssm_mixer(
    p: PyTree,
    u: jax.Array,  # (B, 1, D)
    cache: SSMLayerCache,
    cfg: ModelConfig,
) -> Tuple[jax.Array, SSMLayerCache]:
    dims = ssm_dims(cfg)
    h, pdim, n = dims["n_heads"], dims["p"], dims["n"]
    dt_ = u.dtype
    bsz = u.shape[0]
    zxbcdt = u @ p["in_proj"].astype(dt_)
    z, x, b_mat, c_mat, dt_raw = _split_in_proj(zxbcdt, cfg)
    xbc = jnp.concatenate([x, b_mat, c_mat], axis=-1)  # (B,1,conv_dim)
    window = jnp.concatenate([cache.conv, xbc], axis=1)  # (B,K,conv)
    w = p["conv_w"].astype(dt_)
    conv_out = jnp.sum(window * w[None, :, :], axis=1, keepdims=True)
    conv_out = jax.nn.silu(
        (conv_out + p["conv_b"].astype(dt_)[None, None, :]).astype(jnp.float32)
    ).astype(dt_)
    x, b_mat, c_mat = jnp.split(
        conv_out, [dims["d_inner"], dims["d_inner"] + n], axis=-1
    )
    xh = x.reshape(bsz, h, pdim).astype(jnp.float32)
    dt = jax.nn.softplus(dt_raw[:, 0].astype(jnp.float32) + p["dt_bias"])  # (B,H)
    a = -jnp.exp(p["a_log"])
    decay = jnp.exp(dt * a[None, :])  # (B,H)
    bv = b_mat[:, 0].astype(jnp.float32)  # (B,N)
    cv = c_mat[:, 0].astype(jnp.float32)
    outer = jnp.einsum("bn,bhp->bhnp", bv, xh * dt[..., None])
    state = cache.state * decay[:, :, None, None] + outer
    y = jnp.einsum("bn,bhnp->bhp", cv, state)
    y = y + xh * p["d_skip"][None, :, None]
    y = y.reshape(bsz, 1, dims["d_inner"]).astype(dt_)
    y = y * jax.nn.silu(z.astype(jnp.float32)).astype(dt_)
    y = L.rmsnorm(y, p["ssm_norm_scale"], cfg.rms_eps)
    out = y @ p["out_proj"].astype(dt_)
    new_cache = SSMLayerCache(conv=window[:, 1:], state=state)
    return out, new_cache


# ---------------------------------------------------------------------------
# Pure-SSM decoder LM (mamba2-370m): norm -> mixer -> residual, no MLP.
# ---------------------------------------------------------------------------


class MambaCache(NamedTuple):
    layers: SSMLayerCache  # stacked (L, ...) in each leaf
    next_pos: jax.Array


def init_block(key: jax.Array, cfg: ModelConfig) -> PyTree:
    return {
        "ssm_norm": jnp.ones((cfg.d_model,), cfg.param_dtype),
        "mixer": init_ssm_mixer(key, cfg),
    }


def init_params(key: jax.Array, cfg: ModelConfig) -> PyTree:
    k_embed, k_blocks, k_head = jax.random.split(key, 3)
    block_keys = jax.random.split(k_blocks, cfg.n_layers)
    blocks = jax.vmap(lambda k: init_block(k, cfg))(block_keys)
    params = {
        "embed": L.embed_init(k_embed, cfg.vocab_size, cfg.d_model,
                              cfg.param_dtype),
        "blocks": blocks,
        "final_norm": jnp.ones((cfg.d_model,), cfg.param_dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(
            k_head, cfg.d_model, cfg.vocab_size, scale=0.02,
            dtype=cfg.param_dtype,
        )
    return params


def forward_hidden(params, cfg: ModelConfig, tokens: jax.Array):
    h = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    h = L.shard_activations(h, cfg)

    def body(carry, p):
        x = carry
        normed = L.rmsnorm(x, p["ssm_norm"], cfg.rms_eps)
        x = x + apply_ssm_mixer(p["mixer"], normed, cfg)
        return L.shard_activations(x, cfg), None

    if cfg.remat == "block":
        body = jax.checkpoint(body)
    h, _ = tfm.scan_or_loop(body, h, params["blocks"], scan=cfg.scan_layers,
                            unroll=cfg.scan_unroll)
    return L.rmsnorm(h, params["final_norm"], cfg.rms_eps)


def loss_fn(params, cfg: ModelConfig, batch: Dict[str, jax.Array]):
    h = forward_hidden(params, cfg, batch["tokens"])
    lm_head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    loss, n_tok = L.chunked_cross_entropy(
        h, lm_head, batch["labels"], cfg.loss_chunk
    )
    return loss, {"loss": loss, "tokens": n_tok}


def init_cache(cfg: ModelConfig, batch: int, capacity: int = 0) -> MambaCache:
    del capacity  # O(1) state: capacity-free
    single = init_layer_cache(cfg, batch)
    stacked = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (cfg.n_layers,) + x.shape).copy(),
        single,
    )
    return MambaCache(layers=stacked, next_pos=jnp.zeros((batch,), jnp.int32))


def prefill(params, cfg: ModelConfig, tokens: jax.Array, capacity: int = 0):
    """Forward over the prompt, carrying per-layer final states into a cache."""
    h = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    bsz, s = tokens.shape

    def body(carry, p):
        x = carry
        normed = L.rmsnorm(x, p["ssm_norm"], cfg.rms_eps)
        out, state = apply_ssm_mixer(
            p["mixer"], normed, cfg, return_state=True
        )
        x = x + out
        # conv tail: reconstruct last K-1 conv inputs for decode continuity
        dt_ = normed.dtype
        zxbcdt = normed[:, -(_CONV_K - 1):] @ p["mixer"]["in_proj"].astype(dt_)
        z, xc, b_mat, c_mat, _ = _split_in_proj(zxbcdt, cfg)
        conv_tail = jnp.concatenate([xc, b_mat, c_mat], axis=-1)
        return x, SSMLayerCache(conv=conv_tail, state=state)

    h, layer_caches = tfm.scan_or_loop(
        body, h, params["blocks"], scan=cfg.scan_layers,
        unroll=cfg.scan_unroll,
    )
    h = L.rmsnorm(h, params["final_norm"], cfg.rms_eps)
    lm_head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = h[:, -1].astype(jnp.float32) @ lm_head.astype(jnp.float32)
    cache = MambaCache(
        layers=layer_caches,
        next_pos=jnp.full((bsz,), s, jnp.int32),
    )
    return logits, cache


def decode_step(params, cfg: ModelConfig, cache: MambaCache, token: jax.Array):
    h = jnp.take(params["embed"], token, axis=0).astype(cfg.dtype)

    def body(carry, xs):
        x = carry
        p, lc = xs
        normed = L.rmsnorm(x, p["ssm_norm"], cfg.rms_eps)
        out, new_lc = decode_ssm_mixer(p["mixer"], normed, lc, cfg)
        return x + out, new_lc

    h, new_layers = tfm.scan_or_loop(
        body, h, (params["blocks"], cache.layers), scan=cfg.scan_layers,
        unroll=cfg.scan_unroll,
    )
    h = L.rmsnorm(h, params["final_norm"], cfg.rms_eps)
    lm_head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = h[:, 0].astype(jnp.float32) @ lm_head.astype(jnp.float32)
    return logits, MambaCache(layers=new_layers, next_pos=cache.next_pos + 1)
