"""Front end to host loop, the dispatch thread's own work: mean CPU time of
that thread over one `genie.dispatch` span (its `host_cpu_us` stat):
stacking, hashing, part and merge launches, scattering results and the
callbacks it runs.  Time the thread spends blocked on the device, in a
read of a result or in a launch queued behind running programs, is not
CPU time and does not count."""
import scopes


def read(ctx):
    a = scopes.analyse(ctx.window)
    cpu_us = [d.stats["host_cpu_us"] for d in (a.dispatches if a else ())
              if "host_cpu_us" in d.stats]
    return sum(cpu_us) / len(cpu_us) * 1e-3 if cpu_us else None
