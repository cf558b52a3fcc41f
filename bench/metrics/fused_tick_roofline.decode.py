"""The fused decode-tick kernel (`<mode>_<cell>_decode_tick`) against its
roofline: least time (ops or bytes of the recurrent matrices over the
chip's peaks, bench/work.py) / its device time per call, percent."""


def read(ctx):
    s, n = ctx.trace.per_call_s("ops", lambda k: k.endswith("_decode_tick"))
    if ctx.peak is None or not n:
        return None
    rows = ctx.slots // ctx.chips
    least = ctx.work.least_seconds(
        ctx.work.decode_tick_work(ctx.cfg, rows), ctx.peak)
    return 100.0 * least / s
