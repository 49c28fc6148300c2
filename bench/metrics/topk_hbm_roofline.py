"""Fused match kernel: the least time the chip's HBM bandwidth allows for
the fused kernel's work in the traced window, over the kernel's own device
time (its custom call, named by the configuration's `match_kernel`).

The least bytes (the configuration's `least_bytes`) count the same work
whatever implements it: every stored signature read once at whole bytes,
the query rows, and k ids and counts out per row.  The kernel reads at
least that much, so the share cannot pass 100%."""


def read(ctx):
    ns = ctx.window.kernel_ns(ctx.cfg["match_kernel"])
    if not ns or not ctx.least_bytes:
        return None
    least_s = ctx.least_bytes / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (ns * 1e-9)
