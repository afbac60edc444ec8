"""One call of the flash-attention forward kernel on a (batch, seq) block of
causal attention: QK^T and PV over the causal half of the score matrix,
reading Q, K and V and writing O once in bfloat16."""
from __future__ import annotations

from typing import Any, Dict, Tuple

from chipbench import families

BF16 = 2


def per_call(config: Dict[str, Any], batch: int, seq_len: int
             ) -> Tuple[float, float]:
    """(FLOPs, bytes) of one layer's attention forward."""
    h, kvh, hd = families.load(config).attention_heads(config)
    flops = 2 * 2.0 * batch * h * seq_len * seq_len * hd / 2
    bytes_ = BF16 * batch * seq_len * hd * (2 * h + 2 * kvh)
    return flops, bytes_
