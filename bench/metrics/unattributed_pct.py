"""Device, attribution: the share of busy time that bench/scopes.py gives
no `genie.*` scope.  The scope readers (`compact_us_per_query`, ...) are
only as sound as this is small: time of ops the rules cannot place falls
here, not into a scope."""
import scopes


def read(ctx):
    a = scopes.analyse(ctx.window)
    if a is None or not a.busy_ns:
        return None
    return 100 * a.scope_ns.get(scopes.UNATTRIBUTED, 0.0) / a.busy_ns
