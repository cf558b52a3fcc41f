"""Model FLOP utilization of decode over the traced window: tokens
generated x the model's FLOPs per token (recurrent matrices and head, from
the shapes) / (window x chips x bf16 peak), percent."""


def read(ctx):
    if ctx.peak is None or not ctx.host["tokens"]:
        return None
    flops = ctx.host["tokens"] * ctx.work.model_flops_per_token(ctx.cfg)
    return 100.0 * flops / (ctx.trace.window_s * ctx.chips
                            * ctx.peak["bf16_flops"])
