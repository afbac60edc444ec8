"""Model FLOPs utilization of the whole step: model FLOPs per token (as
the configuration's family counts them) times the tokens of the traced
steps, over the traced window times the chips' peak bf16 FLOP/s."""
from chipbench import families


def read(ctx):
    fam = families.load(ctx.config)
    flops = fam.flops_per_token(ctx.config, ctx.seq_len)
    done = flops * ctx.tokens_per_step * ctx.steps
    return 100.0 * done / (ctx.window_s * ctx.chips
                           * ctx.peaks["bf16_flops_per_s"])
