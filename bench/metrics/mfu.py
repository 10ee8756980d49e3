"""Model FLOP utilization of the whole step: the operations a step
requires per token (the configuration's reference module counts them from
shapes) times tokens per second, over the chips' bf16 peak, in percent."""


def read(ctx):
    if ctx.peak_flops is None:
        return None
    return 100.0 * ctx.flops_per_token * ctx.tokens_per_s \
        / (ctx.chips * ctx.peak_flops)
