"""Device ms per period of a compressed cohort's compression: the
operations under the program's ``paota.compress`` scope in the traced
window (error-feedback compensation with the resumed residuals, support
pick, int8 stochastic quantisation and scale, residual re-sparsify),
averaged over the chips. Nothing where the compiled scan names no such
scope."""
import scopes

SCOPE = "paota.compress"


def read(ctx):
    if not any(SCOPE in v for v in scopes.stage_map(ctx).values()):
        return None
    return scopes.stage_ms(ctx, [SCOPE])
