"""Device ms per period of the writes of the trained rows into the
carry's planes: the operations under the program's ``paota.carry_write``
scope in the traced window, averaged over the chips."""
import scopes


def read(ctx):
    return scopes.stage_ms(ctx, ["paota.carry_write"])
