"""The low-rank update of one hot step, both kernels together, summed over
every low-rank leaf of the configuration: R = P^T G and the
back-projection P N (two d x r x n products per slice), reading W, G,
P and the two moments once and writing W' and the moments once, all in
float32.  Each matrix is oriented so its smaller side d is projected, at
rank min(r, d)."""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

from chipbench import weights as weights_lib

F32 = 4


def slices(config: Dict[str, Any], rank: int) -> List[Tuple[int, int, int, int]]:
    """(count, d, n, r) of the projected matrices: count slices of
    oriented shape (d, n) at rank r."""
    out = []
    for leaf in weights_lib.leaves(config):
        if not leaf.lowrank:
            continue
        m, n = leaf.shape[-2:]
        d, n = min(m, n), max(m, n)
        out.append((weights_lib.slices(leaf.shape), d, n, min(rank, d)))
    return out


def per_step(config: Dict[str, Any], rank: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of one step's low-rank update."""
    flops = bytes_ = 0.0
    for count, d, n, r in slices(config, rank):
        flops += count * 2 * (2.0 * d * n * r)
        bytes_ += count * F32 * (3 * d * n + d * r + 4 * r * n)
    return flops, bytes_
