"""Device ms per period of local training inside the real scan: the
operations under the program's ``paota.train`` scope in the traced
window, averaged over the chips."""
import scopes


def read(ctx):
    return scopes.stage_ms(ctx, ["paota.train"])
