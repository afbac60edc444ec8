"""Weights made from the seed, on the device, by the benchmark itself.

The tree has the layout of a dense pre-norm decoder with its layers
stacked on a leading axis (the layout the program trains and the reference
reads).  Leaf ``i`` of the flattened tree is drawn from ``fold_in(key, i)``
alone, so one leaf can be made again on its own, bit for bit, without
holding the others.  Matrices and biases are N(0, 0.02) (the published
``initializer_range`` of both configurations); norm scales are 1.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

STD = 0.02


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


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def leaves(config: Dict[str, Any]) -> List[Tuple[str, Tuple[int, ...]]]:
    """(path, shape) of every leaf, in the flattened order of the tree."""
    flat, _ = jax.tree_util.tree_flatten_with_path(
        shapes(config), is_leaf=_is_shape
    )
    return [(jax.tree_util.keystr(p), s) for p, s in flat]


def leaf_value(key: jax.Array, index: int, path: str,
               shape: Tuple[int, ...]) -> jax.Array:
    if path.endswith("norm']"):
        return jnp.ones(shape, jnp.float32)
    k = jax.random.fold_in(key, index)
    return STD * jax.random.normal(k, shape, jnp.float32)


def make_key(words: Tuple[int, int]) -> jax.Array:
    return jnp.asarray(words, dtype=jnp.uint32)


@functools.lru_cache(maxsize=None)
def _init_fn(spec: Tuple):
    layout = [(p, s) for p, s in spec]

    @jax.jit
    def init(key):
        return [leaf_value(key, i, p, s) for i, (p, s) in enumerate(layout)]

    return init


def init(key: jax.Array, config: Dict[str, Any]) -> Dict[str, Any]:
    """Every weight, float32, in one jitted call on the default device."""
    layout = leaves(config)
    values = _init_fn(tuple(layout))(key)
    _, treedef = jax.tree_util.tree_flatten(shapes(config), is_leaf=_is_shape)
    return jax.tree_util.tree_unflatten(treedef, values)
