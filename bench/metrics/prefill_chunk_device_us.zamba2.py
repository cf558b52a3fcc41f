"""Device time of one bucket-padded prefill chunk (program
`jit_prefill_slot`) in the zamba2 cell, averaged over its runs,
microseconds."""


def read(ctx):
    s, n = ctx.trace.per_call_s("modules", lambda k: k == "jit_prefill_slot")
    return 1e6 * s if n else None
