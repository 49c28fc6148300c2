"""Host prep, hashing (core/lsh): device time of the ops in the program's
`genie.hash` scope, or of the eager modules launched inside its `genie.hash`
span, per query row answered (bench/scopes.py)."""
import scopes


def read(ctx):
    a = scopes.analyse(ctx.window)
    return None if a is None else a.scope_per_row_us("genie.hash", ctx.rows)
