"""Backend dispatch for paged decode attention
(models/attention.paged_decode_attention routes here).

On TPU with aligned shapes: the Pallas kernel.  Off-alignment, or on CPU
(this container), the jnp reference -- same contract as every other kernel
family, so configs that request the kernel path still run everywhere.

Alignment gate (``_aligned``): the kernel streams one (ps, D) page tile per
grid step out of a (P, ps, KVH*D) view of the pool, so it wants the page
size on a sublane multiple and the head dim on a whole lane tile (128);
anything else (ragged test pages, 64-wide heads) takes the reference.  ``force_pallas=True`` (tests) bypasses the backend check but
NOT the alignment gate -- off-alignment parity is exactly what the gate
exists to avoid having to support in Mosaic.
"""
from __future__ import annotations

import jax

from repro.kernels.flash_attention_decode.kernel import (
    paged_decode_attention_kernel,
)
from repro.kernels.flash_attention_decode.ref import (
    paged_decode_attention_ref,
)

_SUBLANE = 8
_LANE = 128


def _aligned(page_size: int, head_dim: int) -> bool:
    return page_size % _SUBLANE == 0 and head_dim % _LANE == 0


def paged_decode_attention(
    q: jax.Array,  # (B, 1, H, D)
    pages_k: jax.Array,  # (P, ps, KVH, D)
    pages_v: jax.Array,
    page_table: jax.Array,  # (B, MP) int32
    seq_lens: jax.Array,  # (B,) int32
    *,
    window: int = 0,
    force_pallas: bool = False,
    interpret: bool = False,
) -> jax.Array:
    ps, d = pages_k.shape[1], pages_k.shape[3]
    use_kernel = (
        (jax.default_backend() == "tpu" or force_pallas)
        and _aligned(ps, d)
    )
    if use_kernel:
        return paged_decode_attention_kernel(
            q, pages_k, pages_v, page_table, seq_lens,
            window=window, interpret=interpret,
        )
    return paged_decode_attention_ref(
        q, pages_k, pages_v, page_table, seq_lens, window=window
    )
