"""The accumulation-only packed GEMV of prefill (`<mode>_packed_gemv`,
one per prompt token and layer) against its roofline: least time of a
1-row activation against one h-side matrix (bench/work.py) / its device
time per call, percent."""


def read(ctx):
    s, n = ctx.trace.per_call_s("ops", lambda k: k.endswith("_packed_gemv"))
    if ctx.peak is None or not n:
        return None
    least = ctx.work.least_seconds(ctx.work.gemv_work(ctx.cfg, 1), ctx.peak)
    return 100.0 * least / s
