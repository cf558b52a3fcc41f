"""Share of the traced window in which no operation ran on the chip, in
the zamba2 cell: 1 - busy union / window, percent."""


def read(ctx):
    t = ctx.trace
    return 100.0 * (1.0 - t.busy_s / t.window_s) if t.window_s > 0 else None
