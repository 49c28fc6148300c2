"""Fused match kernel: device time of the fused match -> count -> local
top-k kernel (the trace name the configuration gives as `match_kernel`),
its own custom call only, per query row answered."""


def read(ctx):
    ns = ctx.window.kernel_ns(ctx.cfg["match_kernel"])
    if not ns or not ctx.rows:
        return None
    return ns * 1e-3 / ctx.rows
