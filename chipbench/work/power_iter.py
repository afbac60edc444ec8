"""The power iterations of one refresh step, counted from the configuration's
shapes.  Every projected matrix slice, oriented (d, n) with d its smaller
side, refreshes at a refresh step (one refresh group): its sketch Q has
width kp = min(k + oversample, d), with k = min(pool x r, d) for SARA's
pool (k = r without one) at r = min(rank, d), and it runs ``power_iters``
fused steps Y = G (G^T Q), none where kp spans all of d (the program's
``core/svd.clamp_sketch``).  Per slice and step: 4 d n kp FLOPs (the two
products), and at least G read once, Q read and Y written, in float32."""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

from chipbench.work import optimizer_update

F32 = 4


def chains(config: Dict[str, Any], rank: int
           ) -> List[Tuple[int, int, int, int, int]]:
    """(count, d, n, kp, power_iters) of every projected leaf."""
    opt = config["optimizer"]
    pool = opt["sara_pool_factor"] if "sara" in opt["name"] else 1
    out = []
    for count, d, n, r in optimizer_update.slices(config, rank):
        k = min(pool * r, d)
        kp = min(k + opt["svd_oversample"], d)
        iters = 0 if kp >= d else opt["svd_power_iters"]
        out.append((count, d, n, kp, iters))
    return out


def per_step(config: Dict[str, Any], rank: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of one refresh step's power iterations."""
    flops = bytes_ = 0.0
    for count, d, n, kp, iters in chains(config, rank):
        flops += count * iters * 4.0 * d * n * kp
        bytes_ += count * iters * F32 * (d * n + 2 * d * kp)
    return flops, bytes_
