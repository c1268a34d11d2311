"""Per cent of the window's time in which no operation ran on the
device: 1 - (device busy seconds a round x the window's rounds / the
window's seconds).  The busy seconds a round are the union of the "XLA
Ops" intervals over the traced rounds, divided by their number.  The
traced rounds themselves are not the window: the profiler slows every
host-to-device transfer, so their own idle share reads far higher than
an untraced round's.  Nothing to read where the trace holds no device
operation."""
from harness import trace


def read(ctx):
    tr = ctx.trace
    if not tr or not tr["steps"] or not tr["device_ops"] or not ctx.rounds:
        return None
    lo, hi = trace.window(tr)
    busy_s = 1e-9 * trace.busy_ns(tr["device_ops"], lo, hi) / len(tr["steps"])
    return 100.0 * (1.0 - busy_s * ctx.rounds / ctx.window_s)
