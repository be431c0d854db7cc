"""Share of the traced window in which no operation ran on the device,
averaged over the cell's chips (trace: union of the ``XLA Ops`` events)."""
import devtrace


def read(ctx):
    return 100.0 * (1.0 - devtrace.busy_s(ctx.trace) / ctx.trace.window_s)
