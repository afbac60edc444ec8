"""Mixture-of-Experts FFN (deepseek-moe-16b, olmoe-1b-7b).

Dropless token dispatch via sort + ``lax.ragged_dot`` (the Megablocks/MaxText
pattern adapted to pure JAX):

  1. router scores -> top-k experts per token (+ renormalized weights),
  2. flatten (token, expert) pairs, sort by expert id,
  3. one ragged GEMM per projection over expert-grouped rows (no capacity
     factor, no one-hot dispatch tensors, no dropped tokens),
  4. scatter-add back with routing weights.

TPU mapping (DESIGN.md §4): tokens stay data-parallel -- routing, sort and
ragged GEMMs are *local* to each data shard (no global all-to-all); expert
weights are sharded over the ``model`` axis on d_ff (per-expert tensor
parallelism), which XLA SPMD handles like a dense MLP.  An EP variant
(experts sharded over ``model``, all-to-all dispatch) is evaluated as a §Perf
iteration.

DeepSeek's 2 shared experts are fused into one dense SwiGLU of width
``n_shared * d_ff`` (mathematically identical: outputs of always-active
experts sum).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import transformer as tfm

PyTree = Any


def init_moe_mlp(key: jax.Array, cfg: ModelConfig) -> PyTree:
    d, ff, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    dt = cfg.param_dtype
    ks = jax.random.split(key, 5)
    down_scale = 1.0 / ((ff * 2 * cfg.n_layers) ** 0.5)

    def expert_stack(k, m, n, scale=None):
        return jax.vmap(
            lambda kk: L.dense_init(kk, m, n, scale=scale, dtype=dt)
        )(jax.random.split(k, e))

    p = {
        "router_w": L.dense_init(ks[0], d, e, scale=0.02, dtype=jnp.float32),
        "experts": {
            "gate_proj": expert_stack(ks[1], d, ff),
            "up_proj": expert_stack(ks[2], d, ff),
            "down_proj": expert_stack(ks[3], ff, d, scale=down_scale),
        },
    }
    if cfg.n_shared_experts:
        shared_cfg = cfg.with_(mlp_kind="swiglu")
        p["shared_mlp"] = L.init_mlp(
            ks[4], shared_cfg, d_ff=cfg.n_shared_experts * ff
        )
    return p


def apply_moe_mlp(
    p: PyTree, x: jax.Array, cfg: ModelConfig
) -> Tuple[jax.Array, jax.Array]:
    """Dispatch: EP ``shard_map`` on a mesh, local ragged_dot otherwise
    (also inside a region that is already manual, where each rank holds
    whole experts)."""
    mesh = jax.sharding.get_abstract_mesh()
    if (mesh.empty or mesh.size == 1 or "model" not in mesh.axis_names
            or L.manual_axis_names(mesh)):
        return _apply_moe_local(p, x, cfg)
    return _apply_moe_ep(p, x, cfg, mesh)


def _apply_moe_local(
    p: PyTree, x: jax.Array, cfg: ModelConfig
) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (out (B, S, D), aux_loss scalar).

    Aux loss: switch-style load balancing, E * sum_e f_e * p_e  with f_e the
    fraction of routed (token, slot) pairs on expert e and p_e the mean router
    probability of e.
    """
    b, s, d = x.shape
    t = b * s
    k = cfg.moe_top_k
    e = cfg.n_experts
    dt = x.dtype
    xf = x.reshape(t, d)

    scores = (xf.astype(jnp.float32) @ p["router_w"]).astype(jnp.float32)
    probs = jax.nn.softmax(scores, axis=-1)  # (T, E)
    top_w, top_i = jax.lax.top_k(probs, k)  # (T, k)
    top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-9)

    # --- load-balancing aux ---
    counts = jnp.zeros((e,), jnp.float32).at[top_i.reshape(-1)].add(1.0)
    f_e = counts / (t * k)
    p_e = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(f_e * p_e)

    # --- dropless dispatch: sort (token, slot) pairs by expert ---
    flat_expert = top_i.reshape(-1)  # (T*k,)
    flat_token = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    flat_w = top_w.reshape(-1)
    order = jnp.argsort(flat_expert)
    tok_sorted = flat_token[order]
    w_sorted = flat_w[order]
    xs = jnp.take(xf, tok_sorted, axis=0)  # (T*k, D)
    group_sizes = counts.astype(jnp.int32)

    ew = p["experts"]
    gate = jax.lax.ragged_dot(xs, ew["gate_proj"].astype(dt), group_sizes)
    up = jax.lax.ragged_dot(xs, ew["up_proj"].astype(dt), group_sizes)
    h = jax.nn.silu(gate.astype(jnp.float32)).astype(dt) * up
    ys = jax.lax.ragged_dot(h, ew["down_proj"].astype(dt), group_sizes)

    y = jnp.zeros((t, d), jnp.float32)
    y = y.at[tok_sorted].add(ys.astype(jnp.float32) * w_sorted[:, None])
    out = y.astype(dt).reshape(b, s, d)

    if "shared_mlp" in p:
        shared_cfg = cfg.with_(mlp_kind="swiglu")
        out = out + L.apply_mlp(p["shared_mlp"], x, shared_cfg)
    return out, aux


# ---------------------------------------------------------------------------
# Expert-parallel path: EP over `model`, FSDP over `data`, replicated dispatch
# ---------------------------------------------------------------------------
#
# On the production mesh the pure-jit path above degenerates: XLA globalizes
# the token argsort/gather across the data axis (measured: 30x traffic blowup
# on deepseek-moe train_4k).  The EP path makes locality explicit:
#
#   * experts sharded over `model` (64/16 = 4 experts per rank), expert d_ff
#     FSDP-sharded over `data` and all-gathered on use (bwd = reduce-scatter
#     via shard_map autodiff);
#   * activations replicated over `model` inside the region (every model rank
#     routes identically and serves only its own experts);
#   * capacity-bounded dispatch (position-in-expert via one-hot cumsum, the
#     t5x pattern), dense (E_loc, cap, d) batched GEMMs on the MXU;
#   * one psum over `model` combines expert partial outputs -- the same
#     collective a dense Megatron MLP needs.
#
# The local path stays dropless (exact); the EP path drops tokens beyond
# ``capacity_factor`` like every production MoE (documented; equality with
# the local path is tested on a small mesh with ample capacity).


def _ep_local_fn(x_loc, router_w, gate_w, up_w, down_w, shared, cfg,
                 dp_axes):
    b_loc, s, d = x_loc.shape
    t = b_loc * s
    k = cfg.moe_top_k
    e = cfg.n_experts
    dt = x_loc.dtype
    m_size = jax.lax.axis_size("model")
    m_rank = jax.lax.axis_index("model")
    e_loc = e // m_size
    cap = int(t * k / e * cfg.moe_capacity_factor) + 1

    # FSDP gather of expert weights over data (bwd: reduce-scatter).
    if dp_axes:
        gate_w = jax.lax.all_gather(gate_w, "data", axis=-1, tiled=True)
        up_w = jax.lax.all_gather(up_w, "data", axis=-1, tiled=True)
        down_w = jax.lax.all_gather(down_w, "data", axis=-2, tiled=True)

    xf = x_loc.reshape(t, d)
    scores = (xf.astype(jnp.float32) @ router_w).astype(jnp.float32)
    probs = jax.nn.softmax(scores, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, k)
    top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-9)

    flat_e = top_i.reshape(-1)  # (T*k,)
    flat_t = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    flat_w = top_w.reshape(-1)
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.float32)  # (T*k, E)
    pos_in_e = (jnp.cumsum(onehot, axis=0) - onehot)  # position per expert
    pos = jnp.sum(pos_in_e * onehot, axis=-1).astype(jnp.int32)  # (T*k,)

    counts = jnp.sum(onehot, axis=0)  # (E,) routed load (pre-drop)
    f_e = counts / (t * k)
    p_e = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(f_e * p_e)
    aux = jax.lax.pmean(aux, ("model",) + tuple(dp_axes))

    mine = (flat_e >= m_rank * e_loc) & (flat_e < (m_rank + 1) * e_loc)
    keep = mine & (pos < cap)
    e_local_idx = jnp.where(keep, flat_e - m_rank * e_loc, e_loc)  # ovf row
    slot = jnp.where(keep, pos, cap)  # overflow slot
    # dispatch buffer: (E_loc+1, cap+1) holding source token ids (T = pad row)
    disp = jnp.full((e_loc + 1, cap + 1), t, jnp.int32)
    disp = disp.at[e_local_idx, slot].set(flat_t)
    wbuf = jnp.zeros((e_loc + 1, cap + 1), jnp.float32)
    wbuf = wbuf.at[e_local_idx, slot].set(flat_w)
    disp = disp[:e_loc, :cap]
    wbuf = wbuf[:e_loc, :cap]

    x_pad = jnp.concatenate([xf, jnp.zeros((1, d), dt)], axis=0)
    xs = x_pad[disp]  # (E_loc, cap, D)
    gate = jnp.einsum("ecd,edf->ecf", xs, gate_w.astype(dt))
    up = jnp.einsum("ecd,edf->ecf", xs, up_w.astype(dt))
    h = jax.nn.silu(gate.astype(jnp.float32)).astype(dt) * up
    ys = jnp.einsum("ecf,efd->ecd", h, down_w.astype(dt))

    out = jnp.zeros((t + 1, d), jnp.float32)
    out = out.at[disp.reshape(-1)].add(
        (ys * wbuf[..., None].astype(dt)).reshape(-1, d).astype(jnp.float32)
    )
    out = out[:t]
    if shared is not None:
        sg, su, sd = shared
        g = xf @ sg.astype(dt)
        u = xf @ su.astype(dt)
        hsh = jax.nn.silu(g.astype(jnp.float32)).astype(dt) * u
        out = out + (hsh @ sd.astype(dt)).astype(jnp.float32)
    out = jax.lax.psum(out.astype(jnp.float32), "model")
    return out.astype(dt).reshape(b_loc, s, d), aux


def _apply_moe_ep(p, x, cfg, mesh):
    from jax.sharding import PartitionSpec as P

    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    batch_ax = dp_axes if len(dp_axes) > 1 else (dp_axes[0] if dp_axes else None)
    ew = p["experts"]
    shared = None
    shared_specs = None
    if "shared_mlp" in p:
        sm = p["shared_mlp"]
        shared = (sm["gate_proj"], sm["up_proj"], sm["down_proj"])
        # shared expert: TP over model on d_ff, psum'd with routed output
        shared_specs = (P(None, "model"), P(None, "model"), P("model", None))

    import functools

    fn = functools.partial(_ep_local_fn, cfg=cfg, dp_axes=dp_axes)
    # wrap to make `shared` a positional pytree (or None); every mesh axis
    # is manual in the region
    out, aux = jax.shard_map(
        lambda x_, rw, gw, uw, dw, sh: fn(x_, rw, gw, uw, dw, sh),
        mesh=mesh,
        in_specs=(
            P(batch_ax, None, None),  # x: batch over dp, replicated on model
            P(),  # router
            P("model", None, "data"),  # gate (E, d, ff)
            P("model", None, "data"),  # up
            P("model", "data", None),  # down (E, ff, d)
            shared_specs,
        ),
        out_specs=(P(batch_ax, None, None), P()),
        check_vma=False,
    )(x, p["router_w"], ew["gate_proj"], ew["up_proj"], ew["down_proj"],
      shared)
    return out, aux


def moe_mlp_fn(p: PyTree, h: jax.Array, cfg: ModelConfig):
    return apply_moe_mlp(p["moe"], h, cfg)


# ---------------------------------------------------------------------------
# MoE decoder LM = transformer scaffolding with the MoE mlp_fn
# ---------------------------------------------------------------------------


def init_block(key: jax.Array, cfg: ModelConfig) -> PyTree:
    k_attn, k_moe = jax.random.split(key)
    p = tfm.init_block(k_attn, cfg.with_(mlp_kind="swiglu"))
    del p["mlp"]
    p["moe"] = init_moe_mlp(k_moe, cfg)
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
            k_head, cfg.d_model, cfg.vocab_size, scale=0.02,
            dtype=cfg.param_dtype,
        )
    return params


def loss_fn(params, cfg: ModelConfig, batch: Dict[str, jax.Array]):
    return tfm.loss_fn(
        params, cfg, batch, mlp_fn=moe_mlp_fn,
        aux_weight=cfg.router_aux_weight,
    )


def prefill(params, cfg: ModelConfig, tokens, **kw):
    return tfm.prefill(params, cfg, tokens, mlp_fn=moe_mlp_fn, **kw)


def decode_step(params, cfg: ModelConfig, cache, token):
    return tfm.decode_step(params, cfg, cache, token, mlp_fn=moe_mlp_fn)
