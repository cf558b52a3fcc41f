"""Time to first token, 95th percentile, ms (host clock): from each
request's due time to the `step()` return that carried its first token,
over the requests due in the untraced part of a traced run's window (one
with no first token by the window's close counts at close - due).  It
spreads too widely from run to run for an end-to-end bound on a one-chip
host (PERF.md, section 2), and is read here as the scheduler's view of
prefill queueing."""


def read(ctx):
    from bench.harness import nearest_rank

    ttft = ctx.host.get("ttft")
    return 1e3 * nearest_rank(ttft, 0.95) if ttft else None
