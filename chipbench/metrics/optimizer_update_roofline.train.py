"""Roofline share of the low-rank update kernels together (projection
P^T G and the fused Adam + back-projection + W' write), per traced step."""
from chipbench import trace
from chipbench.work import optimizer_update

PATTERN = r"galore_project|lowrank_(adam|msgd|update)"


def read(ctx):
    calls, seconds = trace.kernel(ctx.events, PATTERN, ctx.t0, ctx.t1)
    if not calls:
        return None
    flops, bytes_ = optimizer_update.per_step(ctx.config, ctx.rank)
    least, _ = trace.least_seconds(flops, bytes_, ctx.peaks)
    return trace.roofline_share(ctx.steps, least, seconds)
