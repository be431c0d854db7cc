"""Per-client cosine-similarity kernel (TPU Pallas).

For the similarity factor theta_k (eq. 25) the server needs, for every
client k:   cos_k = <dw_k, g> / (||dw_k|| ||g||)
over the full flattened model (D can be 10^6..10^9). One streaming pass
computes the partials  dot_k = sum_d dw[k,d] g[d]  and  nk = sum_d dw[k,d]^2
accumulating in an f32 VMEM block across the D-grid (revisited output
pattern: initialize at stripe 0, accumulate after).

Output: (K, 2) = [dot_k, norm2_k]; the wrapper finishes the division.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.round_stats import out_struct

DEFAULT_BLOCK_D = 512


def _kernel(x_ref, g_ref, out_ref):
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)     # (K, BLOCK_D)
    g = g_ref[...].astype(jnp.float32)     # (1, BLOCK_D)
    dot = jax.lax.dot_general(x, g, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (K, 1)
    n2 = jnp.sum(x * x, axis=1, keepdims=True)                     # (K, 1)
    partial = jnp.concatenate([dot, n2], axis=1)                   # (K, 2)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = partial

    @pl.when(i != 0)
    def _acc():
        out_ref[...] += partial


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def cosine_partials_pallas(deltas: jnp.ndarray, g: jnp.ndarray, *,
                           block_d: int = DEFAULT_BLOCK_D,
                           interpret: bool = False) -> jnp.ndarray:
    """deltas: (K, D); g: (D,) -> (K, 2) [dot_k, ||delta_k||^2]."""
    k, d = deltas.shape
    pad = (-d) % block_d
    if pad:
        deltas = jnp.pad(deltas, ((0, 0), (0, pad)))
        g = jnp.pad(g, (0, pad))
    dp = d + pad
    return pl.pallas_call(
        _kernel,
        grid=(dp // block_d,),
        in_specs=[
            pl.BlockSpec((k, block_d), lambda i: (0, i)),
            pl.BlockSpec((1, block_d), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((k, 2), lambda i: (0, 0)),  # revisited accumulator
        out_shape=out_struct((k, 2), jnp.float32, deltas, g),
        interpret=interpret,
        name="cosine_partials_pallas",
    )(deltas, g[None, :])


# ---------------------------------------------------------------------------
# shard-aware entry point (mesh client axis)
# ---------------------------------------------------------------------------

def cosine_sim_shard(deltas: jnp.ndarray, g: jnp.ndarray, axis_name=None,
                     eps: float = 1e-12) -> jnp.ndarray:
    """Per-client cosines for use INSIDE ``jax.shard_map`` with K laid over
    the mesh client axis/axes.

    deltas: this shard's client deltas — a pytree of (K_local, ...) leaves
    (a bare (K_local, D) matrix is the raveled single-leaf case); g: the
    matching replicated global-direction pytree / (D,) vector. The eq.-25
    reduction runs over the model dims — which every shard holds whole
    under the client-axis layout — so each client's cosine is computed
    entirely on its own shard with NO collective (per-leaf partials are
    accumulated locally, never psum'd); this entry point
    exists to make that contract explicit at shard_map call sites
    (``axis_name`` is accepted for symmetry with the psum-bearing
    reductions and intentionally unused). The math delegates to the ONE
    cosine implementation (``repro.core.power_control.cosine_similarity``,
    the same function the round core's eq.-25 stage calls), so there is no
    second formula to keep in sync.

    Returns (K_local,) cosines (replicated math, shard-local rows).
    """
    del axis_name  # reduction is over D: shard-local by construction
    from repro.core.power_control import cosine_similarity
    return cosine_similarity(deltas, g, eps=eps)
