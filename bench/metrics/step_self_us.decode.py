"""Host time per scheduler iteration outside its child spans,
microseconds: the mean self time of the program's `serve.step` span
(serve/telemetry.py) over the traced window: admission bookkeeping, the
per-slot fan-out of the stream events, retirement and the scrub dispatch.
None where the program keeps no such record or recorded nothing."""


def read(ctx):
    try:
        from repro.serve import telemetry
    except ImportError:
        return None
    row = telemetry.snapshot()["spans"].get("serve.step")
    return 1e6 * row["self_s"] / row["n"] if row and row["n"] else None
