"""Selection, c-PQ candidate compaction (core/cpq.py `_compact_candidates`):
device time of the ops in the program's `genie.compact` scope per query row
answered; bench/scopes.py gives every device op its scope."""
import scopes


def read(ctx):
    a = scopes.analyse(ctx.window)
    return None if a is None else a.scope_per_row_us("genie.compact", ctx.rows)
