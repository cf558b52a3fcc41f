"""Device time of one run of the engine's jitted decode tick (program
`jit_tick`) in the zamba2 cell, averaged over its runs, microseconds."""


def read(ctx):
    s, n = ctx.trace.per_call_s("modules", lambda k: k == "jit_tick")
    return 1e6 * s if n else None
