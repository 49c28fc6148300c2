"""Search on the device: the least time the chip's HBM bandwidth allows for
the dispatches of the traced window, over the device's busy time.

The least bytes (the configuration's `least_bytes`) count the same work
whatever implements it: every stored signature read once at the narrowest
integer width of its domain, the query rows, and k ids and counts out.  A
fused or narrower implementation keeps the numerator, so the share cannot
pass 100%.  It is a floor on HBM time, not a target: the VPU's peak for the
compares is not published."""


def read(ctx):
    if not ctx.window.busy_ns or not ctx.least_bytes:
        return None
    least_s = ctx.least_bytes / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (ctx.window.busy_ns * 1e-9)
