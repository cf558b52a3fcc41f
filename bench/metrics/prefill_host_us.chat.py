"""Host time of one prefill chunk, microseconds: the mean of the program's
`serve.prefill` span (serve/telemetry.py) over the traced window: the eager
conversions, the dispatch, and on a prompt's last chunk the first-token
commit and its blocking read.  None where the program keeps no such record
or recorded nothing."""


def read(ctx):
    try:
        from repro.serve import telemetry
    except ImportError:
        return None
    row = telemetry.snapshot()["spans"].get("serve.prefill")
    return 1e6 * row["total_s"] / row["n"] if row and row["n"] else None
