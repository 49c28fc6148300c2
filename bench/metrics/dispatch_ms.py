"""Front end -> host loop: mean time of one call into the tenant backend's
`search`, blocking on its result, over the dispatches of the traced window
(the benchmark's `bench.search` span)."""


def read(ctx):
    if not ctx.dispatches:
        return None
    return ctx.dispatch_seconds / ctx.dispatches * 1e3
