"""The accumulation-only packed GEMVs of a zamba2 decode tick against
their roofline: the least time of every packed matrix of a tick at 8 rows
(bench/models/zamba2_work.py) over the `<mode>_packed_gemv` device seconds
a tick takes (their mean time per launch x the tick's matrices), percent.
A prefill chunk of 8 tokens or fewer runs the same launches at the same
padded 8 rows, so it leaves the mean as it is."""
from bench.models import zamba2_work as W


def read(ctx):
    if ctx.cfg.get("model") != "zamba2":
        return None
    s, n = ctx.trace.per_call_s("ops", lambda k: k.endswith("_packed_gemv"))
    if ctx.peak is None or not n:
        return None
    mats = W.matrices(ctx.cfg)
    least = W.tick_gemv_least_s(ctx.cfg, 8, ctx.peak)
    return 100.0 * least / (s * len(mats))
