"""Decoder-only transformer LM (dense families: llama3, qwen2, granite,
nemotron) plus the shared scaffolding every other family reuses:

  * stacked-parameter blocks + ``lax.scan`` over layers (small HLO at 60L),
  * ring-buffer KV cache with absolute-position masks (global & windowed),
  * train / prefill / decode entry points,
  * chunked cross-entropy loss.

Parameters are plain dicts; block params carry a leading (L,) axis.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_lib
from repro.models import layers as L

PyTree = Any


# ---------------------------------------------------------------------------
# KV cache
# ---------------------------------------------------------------------------


class KVCache(NamedTuple):
    """Ring-buffer KV cache.

    k, v : (L, B, C, KVH, D)  -- C = capacity (window size for sliding-window
            attention, max context otherwise).
    pos  : (B, C) int32       -- absolute position stored in each slot,
            -1 = never written.  Shared across layers (all layers write the
            same slots).  Masking is purely positional, so ring-wrap is safe.
    next_pos : (B,) int32     -- next absolute position to be written.
    """

    k: jax.Array
    v: jax.Array
    pos: jax.Array
    next_pos: jax.Array


def init_kv_cache(
    cfg: ModelConfig, batch: int, capacity: int, n_layers: Optional[int] = None
) -> KVCache:
    nl = n_layers if n_layers is not None else cfg.n_layers
    shape = (nl, batch, capacity, cfg.n_kv_heads, cfg.head_dim)
    return KVCache(
        k=jnp.zeros(shape, cfg.dtype),
        v=jnp.zeros(shape, cfg.dtype),
        pos=jnp.full((batch, capacity), -1, jnp.int32),
        next_pos=jnp.zeros((batch,), jnp.int32),
    )


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------


def init_block(key: jax.Array, cfg: ModelConfig) -> PyTree:
    ks = jax.random.split(key, 8)
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    dt = cfg.param_dtype
    o_scale = 1.0 / ((qd * 2 * cfg.n_layers) ** 0.5)
    p = {
        "attn_norm": jnp.ones((d,), dt),
        "q_proj": L.dense_init(ks[0], d, qd, dtype=dt),
        "k_proj": L.dense_init(ks[1], d, kvd, dtype=dt),
        "v_proj": L.dense_init(ks[2], d, kvd, dtype=dt),
        "o_proj": L.dense_init(ks[3], qd, d, scale=o_scale, dtype=dt),
        "mlp_norm": jnp.ones((d,), dt),
        "mlp": L.init_mlp(ks[4], cfg),
    }
    if cfg.qkv_bias:
        p["q_bias"] = jnp.zeros((qd,), dt)
        p["k_bias"] = jnp.zeros((kvd,), dt)
        p["v_bias"] = jnp.zeros((kvd,), dt)
    return p


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
            k_head, cfg.d_model, cfg.vocab_size, scale=0.02, dtype=cfg.param_dtype
        )
    return params


def lm_head_matrix(params: PyTree, cfg: ModelConfig) -> jax.Array:
    if cfg.tie_embeddings:
        return params["embed"].T
    return params["lm_head"]


# ---------------------------------------------------------------------------
# Attention sub-layer (shared by dense/moe/hybrid/encdec blocks)
# ---------------------------------------------------------------------------


def attn_sublayer(
    p: PyTree,
    x: jax.Array,  # (B, S, D) normed input
    cfg: ModelConfig,
    q_positions: jax.Array,
    kv_positions: jax.Array,
    *,
    kv_override: Optional[Tuple[jax.Array, jax.Array]] = None,
    causal: bool = True,
    window: int = 0,
    rope: bool = True,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Returns (attn_out (B,S,D), (k, v)) -- k/v returned for cache fills.

    ``kv_override``: use the provided (k, v) (already roped/positioned) as
    the attention memory instead of self-derived k/v (decode-from-cache and
    cross-attention paths).
    """
    b, s, d = x.shape
    dt = x.dtype
    q = x @ p["q_proj"].astype(dt)
    if "q_bias" in p:
        q = q + p["q_bias"].astype(dt)
    q = q.reshape(b, s, cfg.n_heads, cfg.head_dim)
    k_self = x @ p["k_proj"].astype(dt)
    v_self = x @ p["v_proj"].astype(dt)
    if "k_bias" in p:
        k_self = k_self + p["k_bias"].astype(dt)
        v_self = v_self + p["v_bias"].astype(dt)
    k_self = k_self.reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    v_self = v_self.reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    if rope:
        q = L.apply_rope(q, q_positions, cfg.rope_theta)
        k_self = L.apply_rope(k_self, q_positions, cfg.rope_theta)
    if kv_override is not None:
        k_mem, v_mem = kv_override
    else:
        k_mem, v_mem = k_self, v_self
    out = attn_lib.attention(
        q, k_mem, v_mem, q_positions, kv_positions,
        causal=causal, window=window, impl=cfg.attn_impl,
        chunk_q=cfg.attn_chunk_q, chunk_kv=cfg.attn_chunk_kv,
    )
    out = out.reshape(b, s, cfg.q_dim) @ p["o_proj"].astype(dt)
    return out, (k_self, v_self)


# ---------------------------------------------------------------------------
# Dense block (pre-norm attn + MLP)
# ---------------------------------------------------------------------------


def default_mlp_fn(p: PyTree, h: jax.Array, cfg: ModelConfig):
    """(block_params, normed hidden) -> (mlp_out, aux_scalar)."""
    return L.apply_mlp(p["mlp"], h, cfg), jnp.zeros((), jnp.float32)


def dense_block(
    p: PyTree,
    x: jax.Array,
    cfg: ModelConfig,
    positions: jax.Array,
    kv_positions: jax.Array,
    kv_override=None,
    mlp_fn=default_mlp_fn,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array], jax.Array]:
    h = L.rmsnorm(x, p["attn_norm"], cfg.rms_eps)
    attn_out, kv = attn_sublayer(
        p, h, cfg, positions, kv_positions,
        kv_override=kv_override, window=cfg.attn_window,
    )
    x = x + attn_out
    h = L.rmsnorm(x, p["mlp_norm"], cfg.rms_eps)
    mlp_out, aux = mlp_fn(p, h, cfg)
    x = x + mlp_out
    return x, kv, aux


# ---------------------------------------------------------------------------
# Forward passes (scan over layers)
# ---------------------------------------------------------------------------


def scan_or_loop(body, carry, xs, *, scan: bool, unroll: int = 1):
    """``lax.scan`` or an unrolled Python loop over stacked leaves.

    The unrolled form (``cfg.scan_layers=False``) is used by the dry-run so
    XLA cost analysis counts every layer (HloCostAnalysis counts while-loop
    bodies once -- see roofline/analysis.py).  Semantics identical to scan.
    """
    if scan:
        return jax.lax.scan(body, carry, xs, unroll=unroll)
    n = jax.tree_util.tree_leaves(xs)[0].shape[0]
    ys = []
    for i in range(n):
        xi = jax.tree_util.tree_map(lambda x: x[i], xs)
        carry, y = body(carry, xi)
        ys.append(y)
    if ys and ys[0] is not None:
        ys = jax.tree_util.tree_map(lambda *zs: jnp.stack(zs, axis=0), *ys)
    else:
        ys = None
    return carry, ys


def _scan_blocks(block_fn, blocks: PyTree, x: jax.Array, cfg: ModelConfig,
                 collect_kv: bool = False):
    """Run ``block_fn(params_l, x) -> (x, kv, aux)`` over stacked params.

    Returns (x, kvs, aux_sum)."""

    def body(carry, layer_params):
        y, aux_sum = carry
        y, kv, aux = block_fn(layer_params, y)
        y = L.shard_activations(y, cfg)
        return (y, aux_sum + aux), (kv if collect_kv else None)

    if cfg.remat == "block":
        body = jax.checkpoint(body)
    # around the scan, so its slicing of the stacked weights and stacking
    # of their gradients carry the scope too; the recomputation under
    # remat keeps it as well (".../checkpoint/rematted_computation/...")
    with jax.named_scope("blocks"):
        (x, aux_sum), kvs = scan_or_loop(
            body, (x, jnp.zeros((), jnp.float32)), blocks,
            scan=cfg.scan_layers, unroll=cfg.scan_unroll,
        )
    return x, kvs, aux_sum


def embed_tokens(params: PyTree, tokens: jax.Array, cfg: ModelConfig) -> jax.Array:
    with jax.named_scope("embed"):
        h = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
        return L.shard_activations(h, cfg)


def forward_hidden(
    params: PyTree,
    cfg: ModelConfig,
    tokens: jax.Array,  # (B, S)
    *,
    prefix_embeds: Optional[jax.Array] = None,  # (B, P, D) pre-embedded
    collect_kv: bool = False,
    mlp_fn=default_mlp_fn,
) -> Tuple[jax.Array, Any, jax.Array]:
    """Token (+optional prefix) embedding -> blocks -> final norm."""
    h = embed_tokens(params, tokens, cfg)
    if prefix_embeds is not None:
        h = jnp.concatenate([prefix_embeds.astype(cfg.dtype), h], axis=1)
    b, s, _ = h.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))

    def block_fn(p, x):
        return dense_block(p, x, cfg, positions, positions, mlp_fn=mlp_fn)

    h, kvs, aux = _scan_blocks(block_fn, params["blocks"], h, cfg, collect_kv)
    h = L.rmsnorm(h, params["final_norm"], cfg.rms_eps)
    return h, kvs, aux


def loss_fn(
    params: PyTree, cfg: ModelConfig, batch: Dict[str, jax.Array],
    mlp_fn=default_mlp_fn, aux_weight: float = 0.0,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    prefix = batch.get("patch_embeds", batch.get("frame_embeds"))
    h, _, aux = forward_hidden(
        params, cfg, batch["tokens"], prefix_embeds=prefix, mlp_fn=mlp_fn
    )
    labels = batch["labels"]
    if prefix is not None:
        # Prefix positions carry no next-token loss.
        pad = jnp.full(prefix.shape[:2], -1, labels.dtype)
        labels = jnp.concatenate([pad, labels], axis=1)
    with jax.named_scope("head_loss"):
        loss, n_tok = L.chunked_cross_entropy(
            h, lm_head_matrix(params, cfg), labels, cfg.loss_chunk
        )
    total = loss + aux_weight * aux / max(cfg.n_layers, 1)
    return total, {"loss": loss, "aux": aux, "tokens": n_tok}


# ---------------------------------------------------------------------------
# Serving: prefill + decode
# ---------------------------------------------------------------------------


def _fill_cache_from_kvs(
    cache: KVCache, kvs: Tuple[jax.Array, jax.Array], positions: jax.Array
) -> KVCache:
    """Insert prefill KVs (L,B,S,KVH,D) into (possibly larger) cache slots.

    Assumes prefill length S <= capacity; writes slots [0, S).
    """
    k_new, v_new = kvs
    s = k_new.shape[2]
    cap = cache.k.shape[2]
    if s > cap:  # windowed cache: keep only the last `cap` positions
        k_new = k_new[:, :, -cap:]
        v_new = v_new[:, :, -cap:]
        positions = positions[:, -cap:]
        s = cap
    k = jax.lax.dynamic_update_slice_in_dim(cache.k, k_new, 0, axis=2)
    v = jax.lax.dynamic_update_slice_in_dim(cache.v, v_new, 0, axis=2)
    pos = jax.lax.dynamic_update_slice_in_dim(
        cache.pos, positions.astype(jnp.int32), 0, axis=1
    )
    b = positions.shape[0]
    next_pos = jnp.max(positions, axis=1) + 1
    return KVCache(k=k, v=v, pos=pos, next_pos=next_pos.astype(jnp.int32))


def prefill(
    params: PyTree,
    cfg: ModelConfig,
    tokens: jax.Array,
    *,
    prefix_embeds: Optional[jax.Array] = None,
    capacity: Optional[int] = None,
    mlp_fn=default_mlp_fn,
) -> Tuple[jax.Array, KVCache]:
    """Run the full prompt; return (last-token logits (B, V), filled cache)."""
    h, kvs, _ = forward_hidden(
        params, cfg, tokens, prefix_embeds=prefix_embeds, collect_kv=True,
        mlp_fn=mlp_fn,
    )
    b, s, _ = h.shape
    cap = capacity or (cfg.attn_window if cfg.attn_window else s)
    cache = init_kv_cache(cfg, b, cap)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    cache = _fill_cache_from_kvs(cache, kvs, positions)
    logits = (
        h[:, -1].astype(jnp.float32) @ lm_head_matrix(params, cfg).astype(jnp.float32)
    )
    return logits, cache


def decode_step(
    params: PyTree,
    cfg: ModelConfig,
    cache: KVCache,
    token: jax.Array,  # (B, 1) int32
    mlp_fn=default_mlp_fn,
) -> Tuple[jax.Array, KVCache]:
    """One autoregressive step against the cache (B tokens in parallel)."""
    b = token.shape[0]
    h = embed_tokens(params, token, cfg)
    q_pos = cache.next_pos[:, None]  # (B, 1)
    cap = cache.k.shape[2]
    slot = cache.next_pos % cap  # ring write
    new_pos = jax.vmap(
        lambda row, s_, p_: row.at[s_].set(p_)
    )(cache.pos, slot, cache.next_pos)

    def body(carry, xs):
        x = carry
        p, k_l, v_l = xs
        dt = x.dtype
        hnorm = L.rmsnorm(x, p["attn_norm"], cfg.rms_eps)
        q = hnorm @ p["q_proj"].astype(dt)
        k_new = hnorm @ p["k_proj"].astype(dt)
        v_new = hnorm @ p["v_proj"].astype(dt)
        if "q_bias" in p:
            q = q + p["q_bias"].astype(dt)
            k_new = k_new + p["k_bias"].astype(dt)
            v_new = v_new + p["v_bias"].astype(dt)
        q = q.reshape(b, 1, cfg.n_heads, cfg.head_dim)
        k_new = k_new.reshape(b, 1, cfg.n_kv_heads, cfg.head_dim)
        v_new = v_new.reshape(b, 1, cfg.n_kv_heads, cfg.head_dim)
        q = L.apply_rope(q, q_pos, cfg.rope_theta)
        k_new = L.apply_rope(k_new, q_pos, cfg.rope_theta)
        # where-mask ring write: elementwise, so a capacity-dim-sharded
        # cache updates WITHOUT the all-gather a dynamic scatter would force
        wmask = (
            jax.lax.broadcasted_iota(jnp.int32, (b, k_l.shape[1]), 1)
            == slot[:, None]
        )[:, :, None, None]
        k_upd = jnp.where(wmask, k_new, k_l)
        v_upd = jnp.where(wmask, v_new, v_l)
        out = attn_lib.attention(
            q, k_upd, v_upd, q_pos, new_pos,
            causal=True, window=cfg.attn_window, impl="exact",
        )
        out = out.reshape(b, 1, cfg.q_dim) @ p["o_proj"].astype(dt)
        x = x + out
        hnorm = L.rmsnorm(x, p["mlp_norm"], cfg.rms_eps)
        mlp_out, _ = mlp_fn(p, hnorm, cfg)
        x = x + mlp_out
        return x, (k_upd, v_upd)

    h, (k_all, v_all) = scan_or_loop(
        body, h, (params["blocks"], cache.k, cache.v), scan=cfg.scan_layers,
        unroll=cfg.scan_unroll,
    )
    h = L.rmsnorm(h, params["final_norm"], cfg.rms_eps)
    logits = (
        h[:, 0].astype(jnp.float32)
        @ lm_head_matrix(params, cfg).astype(jnp.float32)
    )
    new_cache = KVCache(
        k=k_all, v=v_all, pos=new_pos, next_pos=cache.next_pos + 1
    )
    return logits, new_cache
