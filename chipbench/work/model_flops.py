"""Model FLOPs per trained token (PaLM accounting, arXiv:2204.02311 app. B):
6 x the matrix parameters (forward, and backward twice), plus causal
attention 6 x layers x seq x heads x head_dim (QK^T and PV over half the
keys on average, forward and backward).  The embedding gather is no
matrix product; the head is.  Recomputation is not counted."""
from __future__ import annotations

from typing import Any, Dict

from chipbench import weights as weights_lib


def matrix_params(config: Dict[str, Any]) -> int:
    d = weights_lib.dims(config)
    qd, kvd = d["H"] * d["hd"], d["KVH"] * d["hd"]
    per_layer = d["D"] * (2 * qd + 2 * kvd) + 3 * d["D"] * d["F"]
    return d["L"] * per_layer + d["D"] * d["V"]


def flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    d = weights_lib.dims(config)
    attn = 6.0 * d["L"] * seq_len * d["H"] * d["hd"]
    return 6.0 * matrix_params(config) + attn
