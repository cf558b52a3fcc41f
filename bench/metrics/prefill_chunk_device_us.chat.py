"""Device time of one prefill chunk (program `jit_prefill_slot`), averaged
over its runs, microseconds."""


def read(ctx):
    s, n = ctx.trace.per_call_s("modules", lambda k: k == "jit_prefill_slot")
    return 1e6 * s if n else None
