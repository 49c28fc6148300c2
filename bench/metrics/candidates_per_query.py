"""Fused match kernel, its output: the candidate slots the fused kernel
writes per query row in one search, the sum of the `candidates` stats (slots
per row, one part each) of the program's `genie.part` spans in the traced
window, over the window's dispatches.  A count matrix would be the corpus's
rows; the reduction reads this many.  None where the part spans carry no
such stat (a program without the fused path)."""
import scopes

PART = "genie.part"


def read(ctx):
    a = scopes.analyse(ctx.window)
    if a is None or not ctx.dispatches:
        return None
    lo, hi = ctx.window.start, ctx.window.end
    slots = [s.stats["candidates"] for s in a.spans
             if s.name == PART and lo <= s.start and s.end <= hi
             and "candidates" in s.stats]
    return sum(slots) / ctx.dispatches if slots else None
