"""Prefill's share of the bf16 peak while it runs: prompt tokens of the
prompts whose prefill ended in the traced window x recurrent FLOPs per
token / (device time of `jit_prefill_slot` x bf16 peak), percent."""


def read(ctx):
    s, n = ctx.trace.per_call_s("modules", lambda k: k == "jit_prefill_slot")
    if ctx.peak is None or not n or not ctx.host["prompt_tokens"]:
        return None
    busy = s * n / ctx.trace.n_devices
    flops = ctx.host["prompt_tokens"] * ctx.work.recurrent_flops_per_token(
        ctx.cfg)
    return 100.0 * flops / (busy * ctx.peak["bf16_flops"])
