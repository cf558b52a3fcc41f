"""Model FLOP utilization of the zamba2 cell over the traced window:
tokens generated x the model's FLOPs per token (every matrix a token
passes through, the shared blocks at each of their calls, and the tied
head, bench/models/zamba2_work.py; 22.1 GFLOP at the published widths) /
(window x chips x bf16 peak), percent."""
from bench.models import zamba2_work as W


def read(ctx):
    if ctx.cfg.get("model") != "zamba2":
        return None
    if ctx.peak is None or not ctx.host["tokens"]:
        return None
    flops = ctx.host["tokens"] * W.model_flops_per_token(ctx.cfg)
    return 100.0 * flops / (ctx.trace.window_s * ctx.chips
                            * ctx.peak["bf16_flops"])
