"""Host ms per period of the driver's serial work in ``advance``: the
``paota.dispatch`` and ``paota.rows`` spans in the traced window, while
the device waits. Nothing where the program has no ``paota.advance``
span; nothing, with the numbers on stderr, where the two spans do not
account for ``paota.advance`` less ``paota.fetch`` within 5%."""
import scopes


def read(ctx):
    span = lambda name: scopes.host_span_seconds(ctx.trace, name)
    advance = span("paota.advance")
    if advance == 0.0:
        return None
    serial = span("paota.dispatch") + span("paota.rows")
    rest = advance - span("paota.fetch")
    if abs(serial - rest) > 0.05 * rest:
        scopes.note(f"paota.dispatch + paota.rows {1e3 * serial:.4f} ms, "
                    f"paota.advance - paota.fetch {1e3 * rest:.4f} ms: "
                    f"apart by more than 5%")
        return None
    return 1e3 * serial / ctx.periods
