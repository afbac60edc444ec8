"""Weights made from the seed, on the device, by the benchmark itself.

The configuration's family (``chipbench.families``) gives the tree's
layout, which leaves take the low-rank update and how many leading axes
of each stack independent matrices.  Leaf ``i`` of the flattened tree is
drawn from ``fold_in(key, i)`` alone, so one leaf can be made again on its
own, bit for bit, without holding the others.  Every leaf is N(0, 0.02)
(the published ``initializer_range`` of the configurations) but those the
family starts at 1 (norm scales).
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from chipbench import families

STD = 0.02


class Leaf(NamedTuple):
    path: str  # as jax.tree_util.keystr writes it
    shape: Tuple[int, ...]
    stack: int  # leading axes that stack independent matrices
    lowrank: bool  # takes the low-rank update
    ones: bool  # starts at 1, else N(0, STD)


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def leaves(config: Dict[str, Any]) -> List[Leaf]:
    """Every leaf of the family's tree, in the tree's flattened order."""
    fam = families.load(config)
    flat, _ = jax.tree_util.tree_flatten_with_path(
        fam.shapes(config), is_leaf=_is_shape
    )
    out = []
    for p, shape in flat:
        path = jax.tree_util.keystr(p)
        leaf = Leaf(path, shape, fam.stack_axes(path), fam.lowrank(path),
                    fam.ones(path))
        if leaf.lowrank and leaf.stack != len(shape) - 2:
            raise ValueError(f"{path} {shape}: a low-rank leaf stacks its "
                             f"last two axes, not {leaf.stack} leading ones")
        out.append(leaf)
    return out


def slices(shape: Tuple[int, ...]) -> int:
    """Matrices in the stack of a low-rank leaf of ``shape``."""
    return math.prod(shape[:-2])


def leaf_value(key: jax.Array, index: int, shape: Tuple[int, ...],
               ones: bool) -> jax.Array:
    if ones:
        return jnp.ones(shape, jnp.float32)
    k = jax.random.fold_in(key, index)
    return STD * jax.random.normal(k, shape, jnp.float32)


def make_key(words: Tuple[int, int]) -> jax.Array:
    return jnp.asarray(words, dtype=jnp.uint32)


@functools.lru_cache(maxsize=None)
def _init_fn(spec: Tuple):
    @jax.jit
    def init(key):
        return [leaf_value(key, i, s, o) for i, (s, o) in enumerate(spec)]

    return init


def init(key: jax.Array, config: Dict[str, Any]) -> Dict[str, Any]:
    """Every weight, float32, in one jitted call on the default device."""
    layout = leaves(config)
    values = _init_fn(tuple((x.shape, x.ones) for x in layout))(key)
    _, treedef = jax.tree_util.tree_flatten(
        families.load(config).shapes(config), is_leaf=_is_shape)
    return jax.tree_util.tree_unflatten(treedef, values)
