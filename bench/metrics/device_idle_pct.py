"""Device: share of the traced window in which no operation ran on it."""


def read(ctx):
    span = ctx.window.end - ctx.window.start
    if span <= 0 or not ctx.window.busy_ns:
        return None
    return 100.0 * (1.0 - ctx.window.busy_ns / span)
