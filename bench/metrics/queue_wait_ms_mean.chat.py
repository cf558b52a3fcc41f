"""Queue wait of a request, from `submit()` to its admission into a slot,
mean, milliseconds, over the admissions of the traced window (about 50):
the program's `serve.queue` records (serve/telemetry.py).  A mean, not a
tail: few of those admissions wait at all, so a p95 of them reads near 0
or hundreds of ms by which burst the window holds.  The wait is part of
time to first token, not of the gap between tokens.  None where the
program keeps no such record or recorded nothing."""


def read(ctx):
    try:
        from repro.serve import telemetry
    except ImportError:
        return None
    waits = telemetry.snapshot()["queue_s"]
    return 1e3 * sum(waits) / len(waits) if waits else None
