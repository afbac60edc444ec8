"""The dense pre-norm decoder (Qwen2, Llama): RMSNorm, grouped-query
attention with rotary embeddings and causal masking, optional q/k/v
biases, SwiGLU MLP, tied or untied head.

Its weights have the layout the program trains: the layers stacked on one
leading axis under ``blocks``.  Matrices and biases are N(0, 0.02) (the
published ``initializer_range``); norm scales are 1.  The attention and
MLP projection matrices (``*_proj``) take the low-rank update.

The reference forward runs in float32 with every matrix product at
``Precision.HIGHEST``, layer by layer (``jax.checkpoint`` per layer,
attention in blocks of queries, the loss in blocks of positions) so that
it fits next to its own optimizer state.

Model FLOPs per trained token follow PaLM's accounting (arXiv:2204.02311
app. B): 6 x the matrix parameters (forward, and backward twice), plus
causal attention 6 x layers x seq x heads x head_dim (QK^T and PV over
half the keys on average, forward and backward).  The embedding gather is
no matrix product; the head is.  Recomputation is not counted.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from chipbench.reference import ein, mm

# the configuration file's sizes and the ModelConfig field each one sets
KEYS = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "num_hidden_layers": "n_layers",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "rms_eps",
    "qkv_bias": "qkv_bias",
    "tie_word_embeddings": "tie_embeddings",
}
ATTN_BLOCK = 512  # queries per attention block
LOSS_BLOCK = 1024  # positions per loss block


# ---------------------------------------------------------------------------
# the weights
# ---------------------------------------------------------------------------


def dims(config: Dict[str, Any]) -> Dict[str, int]:
    c = config
    return dict(
        L=c["num_hidden_layers"], D=c["hidden_size"], F=c["intermediate_size"],
        H=c["num_attention_heads"], KVH=c["num_key_value_heads"],
        hd=c["head_dim"], V=c["vocab_size"],
    )


def shapes(config: Dict[str, Any]) -> Dict[str, Any]:
    d = dims(config)
    L, D, F, V = d["L"], d["D"], d["F"], d["V"]
    qd, kvd = d["H"] * d["hd"], d["KVH"] * d["hd"]
    blocks = {
        "attn_norm": (L, D),
        "q_proj": (L, D, qd),
        "k_proj": (L, D, kvd),
        "v_proj": (L, D, kvd),
        "o_proj": (L, qd, D),
        "mlp_norm": (L, D),
        "mlp": {"gate_proj": (L, D, F), "up_proj": (L, D, F),
                "down_proj": (L, F, D)},
    }
    if config["qkv_bias"]:
        blocks.update(q_bias=(L, qd), k_bias=(L, kvd), v_bias=(L, kvd))
    tree = {"embed": (V, D), "blocks": blocks, "final_norm": (D,)}
    if not config["tie_word_embeddings"]:
        tree["lm_head"] = (D, V)
    return tree


def stack_axes(path: str) -> int:
    return 1 if path.startswith("['blocks']") else 0


def lowrank(path: str) -> bool:
    return "_proj" in path


def ones(path: str) -> bool:
    return path.endswith("norm']")


def vocab(config: Dict[str, Any]) -> int:
    return config["vocab_size"]


# ---------------------------------------------------------------------------
# the reference forward
# ---------------------------------------------------------------------------


def constants(config: Dict[str, Any]) -> Dict[str, Any]:
    d = dims(config)
    return dict(H=d["H"], KVH=d["KVH"], hd=d["hd"],
                theta=float(config["rope_theta"]),
                eps=float(config["rms_norm_eps"]))


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta):
    """Rotary embedding on (B, S, heads, hd): the two halves of each head
    rotate by position x theta^(-2i/hd)."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, prec):
    """Causal grouped-query attention.  q (B, S, H, hd); k, v (B, S, KVH,
    hd); query head h reads key/value head h // (H / KVH)."""
    b, s, h, hd = q.shape
    kvh = k.shape[2]
    q = q.reshape(b, s, kvh, h // kvh, hd)
    blk = min(ATTN_BLOCK, s)

    @jax.checkpoint
    def block(qb, kb, vb, start):
        sc = ein("bqkgd,bskd->bkgqs", qb, kb, prec) / math.sqrt(hd)
        qpos = start + jnp.arange(qb.shape[1])
        mask = qpos[:, None] >= jnp.arange(kb.shape[1])[None, :]
        sc = jnp.where(mask, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return ein("bkgqs,bskd->bqkgd", p, vb, prec)

    outs = []
    for start in range(0, s, blk):
        end = min(start + blk, s)
        outs.append(block(q[:, start:end], k[:, :end], v[:, :end], start))
    return jnp.concatenate(outs, 1).reshape(b, s, h * hd)


def layer(x, p, c, prec):
    b, s, _ = x.shape
    h = rmsnorm(x, p["attn_norm"], c["eps"])
    q, k, v = (mm(h, p[n], prec) for n in ("q_proj", "k_proj", "v_proj"))
    if "q_bias" in p:
        q, k, v = q + p["q_bias"], k + p["k_bias"], v + p["v_bias"]
    q = rope(q.reshape(b, s, c["H"], c["hd"]), c["theta"])
    k = rope(k.reshape(b, s, c["KVH"], c["hd"]), c["theta"])
    v = v.reshape(b, s, c["KVH"], c["hd"])
    x = x + mm(attention(q, k, v, prec), p["o_proj"], prec)
    h = rmsnorm(x, p["mlp_norm"], c["eps"])
    mlp = p["mlp"]
    up = jax.nn.silu(mm(h, mlp["gate_proj"], prec)) * mm(h, mlp["up_proj"], prec)
    return x + mm(up, mlp["down_proj"], prec)


def loss(params, tokens, labels, c, prec="f32"):
    """Mean next-token NLL over the positions whose label is >= 0."""
    x = params["embed"][tokens]

    def body(x, p):
        return jax.checkpoint(lambda x_, p_: layer(x_, p_, c, prec))(x, p), None

    x, _ = jax.lax.scan(body, x, params["blocks"])
    x = rmsnorm(x, params["final_norm"], c["eps"])
    head = params["lm_head"] if "lm_head" in params else params["embed"].T

    @jax.checkpoint
    def nll(xc, yc):
        logits = mm(xc, head, prec)
        lz = jax.scipy.special.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(
            logits, jnp.maximum(yc, 0)[..., None], axis=-1)[..., 0]
        keep = (yc >= 0).astype(jnp.float32)
        return jnp.sum((lz - picked) * keep), jnp.sum(keep)

    s = tokens.shape[1]
    total = count = 0.0
    for start in range(0, s, LOSS_BLOCK):
        t, n = nll(x[:, start:start + LOSS_BLOCK],
                   labels[:, start:start + LOSS_BLOCK])
        total, count = total + t, count + n
    return total / jnp.maximum(count, 1.0)


# ---------------------------------------------------------------------------
# the work
# ---------------------------------------------------------------------------


def matrix_params(config: Dict[str, Any]) -> int:
    d = dims(config)
    qd, kvd = d["H"] * d["hd"], d["KVH"] * d["hd"]
    per_layer = d["D"] * (2 * qd + 2 * kvd) + 3 * d["D"] * d["F"]
    return d["L"] * per_layer + d["D"] * d["V"]


def flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    d = dims(config)
    attn = 6.0 * d["L"] * seq_len * d["H"] * d["hd"]
    return 6.0 * matrix_params(config) + attn


def attention_heads(config: Dict[str, Any]) -> Tuple[int, int, int]:
    d = dims(config)
    return d["H"], d["KVH"], d["hd"]
