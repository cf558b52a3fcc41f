"""Decode ticks dispatched while an earlier tick's sampled ids were still
unread, as a share of all ticks over the traced window, %: the program's
`serve.ticks_ahead` counter over the count of its `serve.tick` span
(serve/telemetry.py).  None where the program keeps no counters (one that
reads every tick before it dispatches the next) or recorded no tick."""


def read(ctx):
    try:
        from repro.serve import telemetry
    except ImportError:
        return None
    snap = telemetry.snapshot()
    counts = snap.get("counts")
    tick = snap["spans"].get("serve.tick")
    if counts is None or not tick or not tick["n"]:
        return None
    return 100.0 * counts.get("serve.ticks_ahead", 0) / tick["n"]
