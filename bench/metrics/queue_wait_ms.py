"""Front-end queue (serve/scheduler.py): mean time a request waits from
`submit` to the start of the dispatch that takes it, over the requests of
the window's dispatches (the `queue_wait_us_sum` and `requests` stats of the
program's `genie.dispatch` spans)."""
import scopes


def read(ctx):
    a = scopes.analyse(ctx.window)
    if a is None:
        return None
    requests = sum(d.stats.get("requests", 0) for d in a.dispatches)
    if not requests:
        return None
    waited_us = sum(d.stats.get("queue_wait_us_sum", 0.0) for d in a.dispatches)
    return waited_us / requests * 1e-3
