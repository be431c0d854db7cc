"""Sliding-window flash attention (forward) — TPU Pallas.

Serves the SWA paths: mixtral-8x22b (native SWA-4096), zamba2's shared
attention block, and the long_500k sliding-window variants of the dense
archs (DESIGN.md §4). Online-softmax flash schedule with explicit VMEM
tiling:

  grid = (B*H, nQ, nJ) — j (kv stripe) innermost, carrying running
  (m, l, acc) in f32 VMEM scratch; out written at the last stripe.

Window structure is exploited STRUCTURALLY, not just by masking: for
window W the kv index map visits only ceil((W+BQ)/BK)+1 stripes per query
block (clamped at the sequence edge; clamp duplicates are masked out via
the raw-index validity test). Compute per q block is O(W + BQ) instead of
O(T) — this is what makes long_500k prefill/decode affordable.

MXU alignment: BQ/BK default 128; head_dim is the minor (lane) dimension.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                 block_q: int, block_k: int, window: Optional[int],
                 n_kv_blocks: int, n_j: int, seq_q: int, seq_kv: int,
                 causal: bool):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # raw kv stripe index (must mirror the index_map arithmetic)
    if window is not None:
        raw = (i * block_q - window) // block_k + j
    else:
        raw = j
    valid_block = (raw >= 0) & (raw < n_kv_blocks)

    q = q_ref[0].astype(jnp.float32)             # (BQ, D)
    k = k_ref[0].astype(jnp.float32)             # (BK, D)
    v = v_ref[0].astype(jnp.float32)             # (BK, D)
    scale = 1.0 / (q.shape[-1] ** 0.5)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    q_pos = i * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    clamped = jnp.clip(raw, 0, n_kv_blocks - 1)
    k_pos = clamped * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = valid_block & (k_pos < seq_kv) & (q_pos < seq_q)
    if causal:
        mask = mask & (k_pos <= q_pos)
    if window is not None:
        mask = mask & (k_pos > q_pos - window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                          # (BQ, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_new = alpha * acc_scr[...] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc_new

    @pl.when(j == n_j - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "window", "causal", "block_q", "block_k", "interpret"))
def swa_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                         window: Optional[int] = None, causal: bool = True,
                         block_q: int = 128, block_k: int = 128,
                         interpret: bool = False) -> jnp.ndarray:
    """q: (BH, T, D); k, v: (BH, S, D) -> (BH, T, D).

    window: sliding-window width (None = full); causal: apply causal mask.
    """
    from jax.experimental.pallas import tpu as pltpu

    bh, t, d = q.shape
    s_kv = k.shape[1]
    pad_q = (-t) % block_q
    pad_k = (-s_kv) % block_k
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0))) if pad_q else q
    kp = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0))) if pad_k else k
    vp = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0))) if pad_k else v
    n_q = (t + pad_q) // block_q
    n_kv = (s_kv + pad_k) // block_k

    if window is not None:
        n_j = (window + block_q) // block_k + 1
        def k_map(b, i, j):
            raw = (i * block_q - window) // block_k + j
            return (b, jnp.clip(raw, 0, n_kv - 1), 0)
    else:
        n_j = n_kv
        def k_map(b, i, j):
            return (b, j, 0)

    kernel = functools.partial(
        _attn_kernel, block_q=block_q, block_k=block_k, window=window,
        n_kv_blocks=n_kv, n_j=n_j, seq_q=t, seq_kv=s_kv, causal=causal)

    out = pl.pallas_call(
        kernel,
        grid=(bh, n_q, n_j),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), k_map),
            pl.BlockSpec((1, block_k, d), k_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t + pad_q, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :t, :]
