"""Roofline share of the compressed superposition
(``gather_superpose_pallas``): least time for its bytes and operations
over its device time per period."""
import kernels
import devtrace


def read(ctx):
    per_chip = devtrace.op_seconds(ctx.trace, r"gather_superpose_pallas")
    if not per_chip:
        return None
    flops, bytes_ = kernels.gather_superpose(ctx.shapes)
    return kernels.roofline_share(flops, bytes_,
                                  max(per_chip) / ctx.periods, ctx.peak)
