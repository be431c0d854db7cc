"""Device operations per period: the operations that ran in the traced
window (control-flow containers such as a scan's ``while`` left out),
averaged over the chips, over the periods the window completed. In a
period bound by latency, the length of its chain of operations."""
import devtrace


def read(ctx):
    if not ctx.trace.devices:
        return None
    lo, hi = ctx.trace.window
    counts = [sum(1 for n, s, e in ev if e > lo and s < hi
                  and devtrace.SUFFIX.sub("", n) not in devtrace.CONTAINERS)
              for ev in ctx.trace.devices.values()]
    return sum(counts) / len(counts) / ctx.periods
