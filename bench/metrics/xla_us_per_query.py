"""Device time outside the two named kernels (compaction, merge, hashing,
pad and transpose) per query row answered: busy time less the match and
histogram kernels."""


def read(ctx):
    if not ctx.rows or not ctx.window.busy_ns:
        return None
    named = (ctx.window.kernel_ns(ctx.cfg["match_kernel"])
             + ctx.window.kernel_ns(ctx.cfg["hist_kernel"]))
    return (ctx.window.busy_ns - named) * 1e-3 / ctx.rows
