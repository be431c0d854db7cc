"""The driver's own local-training call for one period (every row it
trains: the K clients dense, the m slots of a cohort), jitted alone at the
cell's shapes and timed by the host clock with ``block_until_ready``."""


def read(ctx):
    return ctx.train_ms
