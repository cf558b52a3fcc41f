"""Host time per decode tick to copy the ready sampled ids to the host,
microseconds: the mean of the program's `serve.ids_copy` span
(serve/telemetry.py; one a tick) over the traced window.  None where the
program keeps no such record or recorded nothing."""


def read(ctx):
    try:
        from repro.serve import telemetry
    except ImportError:
        return None
    row = telemetry.snapshot()["spans"].get("serve.ids_copy")
    return 1e6 * row["total_s"] / row["n"] if row and row["n"] else None
