"""Mamba2 SSD intra-chunk kernel (TPU Pallas) [arXiv:2405.21060].

The SSD duality splits the selective-scan into (i) a quadratic-in-chunk
"attention-like" part and (ii) a linear cross-chunk state recurrence. Part
(i) is the MXU hot-spot — per (batch, chunk, head):

    decay[i,j] = exp(cum[i] - cum[j]) * causal(i >= j)
    scores     = (C B^T) * decay            # (Q, Q)
    y_intra    = scores @ (x * dt)          # (Q, P)
    tail[j]    = exp(cum[Q-1] - cum[j])
    state      = (B * tail)^T @ (x * dt)    # (N, P)  chunk's state contribution

This kernel fuses all five in one VMEM-resident tile per grid cell
(grid = batch*chunks*heads), with Q/N/P MXU-aligned where the configs
allow (Q=256, N=64/128, P=64). The cross-chunk recurrence stays a
lax.scan on the host graph (it is O(T/Q) and bandwidth-trivial).

Validated against ref.ssd_intra_chunk_ref in interpret mode; the pure-jnp
path in repro.models.ssm remains the default on CPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(cum_ref, b_ref, c_ref, xdt_ref, y_ref, state_ref, decay_ref):
    cum = cum_ref[0].astype(jnp.float32)          # (Q, 1)
    b = b_ref[0].astype(jnp.float32)              # (Q, N)
    c = c_ref[0].astype(jnp.float32)              # (Q, N)
    xdt = xdt_ref[0].astype(jnp.float32)          # (Q, P)
    q = cum.shape[0]

    li = cum                                       # (Q, 1) query decay
    lj = cum.reshape(1, q)                         # (1, Q) key decay
    decay = jnp.exp(jnp.clip(li - lj, -60.0, 0.0))
    causal = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
              >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))
    scores = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    scores = jnp.where(causal, scores * decay, 0.0)
    y = jax.lax.dot_general(scores, xdt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    tail = jnp.exp(jnp.clip(cum[q - 1] - cum, -60.0, 0.0))   # (Q, 1)
    state = jax.lax.dot_general(b * tail, xdt, (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (N, P)

    y_ref[0] = y.astype(y_ref.dtype)
    state_ref[0] = state
    decay_ref[0] = jnp.exp(jnp.clip(cum[q - 1], -60.0, 0.0)).reshape(1, 1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_intra_chunk_pallas(cum, b, c, xdt, *, interpret: bool = False):
    """cum: (G, Q) cumulative log-decay; b, c: (G, Q, N); xdt: (G, Q, P)
    where G = batch*chunks*heads (wrapper-flattened).

    Returns (y (G,Q,P), state (G,N,P), chunk_decay (G,))."""
    g, q = cum.shape
    n, p = b.shape[2], xdt.shape[2]
    y, state, decay = pl.pallas_call(
        _kernel,
        grid=(g,),
        in_specs=[
            pl.BlockSpec((1, q, 1), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, q, n), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, q, n), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, q, p), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, q, p), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, n, p), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((g, q, p), xdt.dtype),
            jax.ShapeDtypeStruct((g, n, p), jnp.float32),
            jax.ShapeDtypeStruct((g, 1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(cum[..., None], b, c, xdt)
    return y, state, decay[:, 0, 0]
