"""How late the load generator submitted the requests due in the traced
window, 95th percentile, ms (host clock).  A starved generator would show
here, not as a fast server."""


def read(ctx):
    from bench.harness import nearest_rank

    lags = ctx.host["lags"]
    return 1e3 * nearest_rank(lags, 0.95) if lags else None
