"""Device ms per period of the operations under no ``paota.`` scope in
the traced window, averaged over the chips: the scan's loop plumbing and
what the compiler adds without an ``op_name`` (loop-carry copies, output
stacking, relayouts). Operations the compiled text does not name count
here too. Their share of the device's busy time, and the kinds of
operation that take most of this metric, go to stderr."""
import devtrace
import scopes

TOP = 6


def read(ctx):
    ms = scopes.stage_ms(ctx, [None])
    busy = devtrace.busy_s(ctx.trace)
    if ms is None or busy == 0.0:
        return ms
    smap = scopes.stage_map(ctx)
    lost = scopes.unresolved_seconds(ctx.trace, smap)
    lost_s = sum(lost) / len(lost)
    scopes.note(f"unresolved operations {1e3 * lost_s:.4f} ms in the "
                f"window, {100 * lost_s / busy:.4f}% of device busy time")
    kinds = scopes.unscoped_kinds(ctx.trace, smap)[:TOP]
    scopes.note("unscoped ms per period by kind: " + ", ".join(
        f"{k} {1e3 * s / ctx.periods:.3f}" for k, s in kinds))
    return ms
