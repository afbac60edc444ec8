"""Roofline share of the flash-attention forward kernel (each layer's
forward, and its recomputation under remat, is one call)."""
from chipbench import trace
from chipbench.work import flash_attention

PATTERN = r"flash_attention"


def read(ctx):
    calls, seconds = trace.kernel(ctx.events, PATTERN, ctx.t0, ctx.t1)
    flops, bytes_ = flash_attention.per_call(ctx.config, ctx.batch,
                                             ctx.seq_len)
    least, _ = trace.least_seconds(flops, bytes_, ctx.peaks)
    return trace.roofline_share(calls, least, seconds)
