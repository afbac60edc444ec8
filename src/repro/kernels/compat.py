"""Shared plumbing for the Pallas TPU kernels.

``CompilerParams``: the Mosaic compiler-parameter type every kernel passes
to ``pallas_call`` (``dimension_semantics`` etc.), re-exported so the
kernels name it in one place.

``pick_block``: safe block-size selection for non-divisible dims.  The old
per-kernel fallback (``bd, bn = d, n`` whenever a dim wasn't divisible by the
requested block) silently promoted the *whole array* into VMEM -- fine for
the ragged test shapes it was written for, a VMEM blow-up for production
shapes like d_ff=11008 with block 512 (11008 % 512 != 0 -> a 4096 x 11008
f32 block is ~180 MB against ~16 MB of VMEM).  ``pick_block`` instead rounds
down to the largest *divisor* of the dim that is a multiple of ``align``
(TPU lane width), and only then falls back to the whole dim (small ragged
shapes where that is the right answer).
"""
from __future__ import annotations

from jax.experimental.pallas.tpu import CompilerParams  # noqa: F401


def pick_block(dim: int, block: int, align: int = 128) -> int:
    """Largest ``align``-multiple divisor of ``dim`` that is <= ``block``;
    returns ``dim`` itself when none exists (then the caller keeps the
    whole dim in VMEM as a single padded block, as before)."""
    block = min(block, dim)
    if dim % block == 0:
        return block
    # Aligned divisors, largest first.  Anything else falls back to the
    # whole dim -- one padded block, the old behavior.  Unaligned divisors
    # are NOT acceptable: Mosaic only tolerates tile misalignment in the
    # final (padded) block of a dim, so a 480-wide block over a 1440 lane
    # dim would mis-tile on hardware even though it divides evenly.
    for b in range(block - block % align, 0, -align):
        if dim % b == 0:
            return b
    return dim
