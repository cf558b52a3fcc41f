"""Bucket padding in the zamba2 cell's prefill over the traced window: the
program's `serve.prefill_pad` counter (pad tokens prefilled,
serve/telemetry.py) over those plus the prompt tokens of the requests whose
prefill ended inside the traced part (the harness's count), percent.  None
where the program keeps no such counter."""


def read(ctx):
    try:
        from repro.serve import telemetry
    except ImportError:
        return None
    pad = telemetry.snapshot().get("counts", {}).get("serve.prefill_pad")
    real = ctx.host["prompt_tokens"]
    if pad is None or pad + real <= 0:
        return None
    return 100.0 * pad / (pad + real)
