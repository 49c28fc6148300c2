"""Candidate reduction (core/plan.py `_fused_candidates_topk`): device time
of the ops in the program's `genie.order` scope, which sorts the fused
kernel's per-tile candidate buffers into the top-k, per query row answered
(bench/scopes.py).  In a configuration served by the fused kernel no other
op carries that scope (the merge of the parts is `genie.merge`)."""
import scopes


def read(ctx):
    a = scopes.analyse(ctx.window)
    return None if a is None else a.scope_per_row_us("genie.order", ctx.rows)
