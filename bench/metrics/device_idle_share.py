"""Share of the traced window in which no operation ran on the device, in
percent (busy time is the union of the device's op intervals)."""


def read(ctx):
    if ctx.trace.window_s <= 0 or not ctx.trace.busy:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)
