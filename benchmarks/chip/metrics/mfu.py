"""Useful local-training operations per second of the traced window over
the chips' bf16 peak. Useful: the uploaders' restarts only (each trains M
steps of the configuration's ``step_flops``); the dense program trains
every row, but only the restarters' rows are kept."""


def read(ctx):
    useful = ctx.restarts * ctx.traffic["local_steps"] * ctx.step_flops
    return 100.0 * useful / (ctx.trace.window_s * ctx.chips
                             * ctx.peak["bf16_flops_per_s"])
