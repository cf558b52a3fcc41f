"""The state traffic of a zamba2 decode tick against its roofline: the
least time of the state bytes a tick moves (every mamba layer's h and conv
tail in and out for every slot, every call's keys and values at the live
slots' contexts; bench/models/zamba2_work.py) over the tick's device time
outside its packed GEMV launches, percent.  The tick is `jit_tick` per
call; its GEMV time is the mean `<mode>_packed_gemv` launch times the
tick's matrices (every tick runs each once; a prefill chunk of 8 tokens
or fewer runs the same launches).  The contexts are the program's
`serve.live_context` counter over its `serve.tick` span count
(serve/telemetry.py); None where the program keeps no such counter."""
from bench.models import zamba2_work as W


def read(ctx):
    if ctx.cfg.get("model") != "zamba2":
        return None
    try:
        from repro.serve import telemetry
    except ImportError:
        return None
    snap = telemetry.snapshot()
    ctxs = snap.get("counts", {}).get("serve.live_context")
    tick = snap["spans"].get("serve.tick")
    if ctx.peak is None or ctxs is None or not tick or not tick["n"]:
        return None
    t_s, t_n = ctx.trace.per_call_s("modules", lambda k: k == "jit_tick")
    g_s, g_n = ctx.trace.per_call_s("ops", lambda k: k.endswith("_packed_gemv"))
    rest = t_s - g_s * len(W.matrices(ctx.cfg))
    if not t_n or not g_n or rest <= 0:
        return None
    nbytes = W.state_bytes(ctx.cfg, ctx.slots, ctxs / tick["n"])
    return 100.0 * nbytes / ctx.peak["hbm_bytes_s"] / rest
