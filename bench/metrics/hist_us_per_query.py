"""Selection: device time of the c-PQ histogram kernel (the trace name the
configuration gives as `hist_kernel`) per query row answered."""


def read(ctx):
    ns = ctx.window.kernel_ns(ctx.cfg["hist_kernel"])
    if not ns or not ctx.rows:
        return None
    return ns * 1e-3 / ctx.rows
