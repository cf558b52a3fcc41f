"""Token events of plain decode ticks per tick, as a share of the slots,
over the traced window (the engine's tick counter and the events its
step() returned)."""


def read(ctx):
    ticks = ctx.host["ticks"]
    if not ticks:
        return None
    return 100.0 * ctx.host["tick_events"] / (ticks * ctx.slots)
