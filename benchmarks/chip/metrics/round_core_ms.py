"""Device ms per period of the round core: the operations under the
program's ``paota.schedule``, ``paota.stats``, ``paota.power`` and
``paota.superpose`` scopes in the traced window (the round kernels
included), averaged over the chips."""
import scopes

ROUND_CORE = ("paota.schedule", "paota.stats", "paota.power",
              "paota.superpose")


def read(ctx):
    return scopes.stage_ms(ctx, ROUND_CORE)
