"""Host time per decode tick spent waiting for the tick to finish before
its sampled ids can be read, microseconds: the mean of the program's
`serve.ids_wait` span (serve/telemetry.py; one a tick) over the traced
window.  None where the program keeps no such record or recorded nothing."""


def read(ctx):
    try:
        from repro.serve import telemetry
    except ImportError:
        return None
    row = telemetry.snapshot()["spans"].get("serve.ids_wait")
    return 1e6 * row["total_s"] / row["n"] if row and row["n"] else None
