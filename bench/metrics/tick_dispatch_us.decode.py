"""Host time to dispatch one decode tick, microseconds: the mean of the
program's `serve.tick` span (serve/telemetry.py) over the traced window.
None where the program keeps no such record or recorded nothing."""


def read(ctx):
    try:
        from repro.serve import telemetry
    except ImportError:
        return None
    row = telemetry.snapshot()["spans"].get("serve.tick")
    return 1e6 * row["total_s"] / row["n"] if row and row["n"] else None
