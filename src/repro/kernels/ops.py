"""jit'd public wrappers for the Pallas kernels.

Lowering policy: each wrapper runs its compiled Pallas kernel where the
computation is lowered for a TPU and a jnp twin (same contract, f32
accumulation) on every other platform. The choice is made per lowering
by ``jax.lax.platform_dependent``, not per process: a reference placed
on the CPU device of a TPU process takes the twin, and a compile for a
described (unattached) TPU topology takes the kernel. Interpret mode is
never chosen here — tests ask the kernels for it explicitly. The wrappers
also expose layout adaptation (GQA head repetition, (B,T,H,D) <->
(BH,T,D)) so the model code stays clean.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.aircomp_sum import (gather_superpose_pallas,
                                       superpose_normalize_pallas)
from repro.kernels.cosine_sim import cosine_partials_pallas
from repro.kernels.round_stats import (compressed_round_stats,
                                       round_stats_jnp, round_stats_pallas,
                                       round_stats_tp)
from repro.kernels.ssd_chunk import ssd_intra_chunk_pallas
from repro.kernels.swa_attention import swa_attention_pallas


def on_tpu(kernel, twin):
    """``kernel()`` where this computation is lowered for a TPU,
    ``twin()`` everywhere else. Both are traced; only the branch for the
    lowering platform reaches the compiler."""
    return jax.lax.platform_dependent(tpu=kernel, default=twin)


def _round_stats_kernel(deltas, g, payload):
    """One compiled ``round_stats_pallas`` call per leaf, each leaf in its
    own shape, accumulated in tree_flatten order (the twin's order)."""
    d_leaves = jax.tree_util.tree_leaves(deltas)
    g_leaves = jax.tree_util.tree_leaves(g)
    p_leaves = (jax.tree_util.tree_leaves(payload) if payload is not None
                else [None] * len(d_leaves))
    dots = dn2 = pn2 = gn2 = None
    for dl, plf, gl in zip(d_leaves, p_leaves, g_leaves):
        stats, g2 = round_stats_pallas(dl, gl, plf)
        if dots is None:
            dots, dn2, gn2 = stats[:, 0], stats[:, 1], g2
            pn2 = stats[:, 2] if payload is not None else None
        else:
            dots, dn2, gn2 = dots + stats[:, 0], dn2 + stats[:, 1], gn2 + g2
            if payload is not None:
                pn2 = pn2 + stats[:, 2]
    return dots, dn2, pn2, gn2


def round_stats(deltas, g, payload=None, tp=None):
    """Fused eq.-25 round stats over a params pytree (raveled = single
    (K, D) leaf): ``(dots, dn2, pn2 | None, gn2)`` in one sweep.

    Compiled Pallas kernel per leaf on TPU; the jnp twin elsewhere (same
    contract, same f32 accumulation).

    ``tp``: intra-client ``TPTopology`` under ``jax.shard_map`` — the
    sweep then runs on the TP-local leaf blocks against a TP-sliced
    global direction and reduces the sharded partials once over
    ``tp.axes`` (see ``kernels.round_stats.round_stats_tp``)."""
    if tp is not None:
        return round_stats_tp(deltas, g, payload, tp,
                              lambda d, gg, p: round_stats(d, gg, p))
    return on_tpu(lambda: _round_stats_kernel(deltas, g, payload),
                  lambda: round_stats_jnp(deltas, g, payload))


def superpose_normalize(stacked: jnp.ndarray, powers: jnp.ndarray,
                        mask: jnp.ndarray, noise: jnp.ndarray,
                        vs_min: float = 1e-12):
    """Fused eq. (6)+(8) for one (K, ...) leaf and its leaf-shaped noise:
    (agg (D,) f32, raw varsigma), D the leaf's size. Compiled kernel on
    TPU, which reads and writes the leaf in its own layout (the caller's
    reshape of the flat aggregate back to the leaf's shape cancels
    against this one); f32-accumulating einsum over the flattened leaf
    elsewhere."""
    def twin():
        # one einsum with f32 accumulation (the convert of a bf16 payload
        # fuses into the contraction — no materialized f32 copy); for f32
        # payloads this is the exact historical op sequence
        bp = (powers * mask).astype(jnp.float32)
        raw = jnp.sum(bp)
        acc = jnp.einsum("k,kd->d", bp,
                         stacked.reshape((stacked.shape[0], -1)),
                         preferred_element_type=jnp.float32)
        agg = (acc + noise.reshape(-1).astype(jnp.float32)) / jnp.maximum(
            raw, vs_min)
        return agg, raw

    def kernel():
        agg, raw = superpose_normalize_pallas(stacked, powers, mask, noise,
                                              vs_min=vs_min)
        return agg.reshape(-1), raw

    return on_tpu(kernel, twin)


def gather_superpose(values, idx, bp, noise, *, d: int, scale=None,
                     vs_min: float = 1e-12):
    """Fused gather-superpose-decompress over the (m, s) compressed cohort
    plane: ((d,) f32 aggregate, raw varsigma). Compiled one-hot-scatter
    kernel on TPU; the scatter + f32 einsum twin elsewhere (the twin's
    decompressed (m, d) rows exist only transiently inside this op — the
    round carry never holds them). ``scale`` folds int8 dequantization
    into the contraction weights; varsigma is the RAW sum of b*p."""
    def twin():
        bp32 = bp.astype(jnp.float32)
        w = bp32 if scale is None else bp32 * scale.astype(jnp.float32)
        raw = jnp.sum(bp32)
        m = values.shape[0]
        rows = jnp.arange(m)[:, None]
        dense = jnp.zeros((m, d), jnp.float32).at[rows, idx].add(
            values.astype(jnp.float32))
        acc = jnp.einsum("k,kd->d", w, dense,
                         preferred_element_type=jnp.float32)
        agg = (acc + noise.astype(jnp.float32)) / jnp.maximum(raw, vs_min)
        return agg, raw

    return on_tpu(lambda: gather_superpose_pallas(
        values, idx, bp, noise, d=d, scale=scale, vs_min=vs_min), twin)


def round_stats_compressed(values, idx, resid, resid_idx, g, scale=None):
    """Round stats over the compressed plane + EF residuals. Pure jnp on
    every backend (gather-bound, no stripe contraction to fuse — see
    ``repro.kernels.round_stats.compressed_round_stats``); routed through
    ops so the round core has one kernel seam."""
    return compressed_round_stats(values, idx, resid, resid_idx, g,
                                  scale=scale)


def aircomp_sum(stacked: jnp.ndarray, bp: jnp.ndarray,
                noise: jnp.ndarray) -> jnp.ndarray:
    """Fused (sum_k bp_k w_k + n)/sum bp_k. stacked (K,D) -> (D,) f32.
    On TPU the round's superposition kernel, with the mask all ones."""
    return on_tpu(
        lambda: superpose_normalize_pallas(stacked, bp, jnp.ones_like(bp),
                                           noise)[0],
        lambda: ref.aircomp_sum_ref(stacked, bp, noise))


def cosine_sim(deltas: jnp.ndarray, g: jnp.ndarray, eps: float = 1e-12):
    """Per-client cos(dw_k, g): (K, D), (D,) -> (K,)."""
    parts = on_tpu(lambda: cosine_partials_pallas(deltas, g),
                   lambda: ref.cosine_partials_ref(deltas, g))
    gn = jnp.sqrt(jnp.maximum(jnp.sum(g.astype(jnp.float32) ** 2), eps))
    return parts[:, 0] / jnp.maximum(jnp.sqrt(jnp.maximum(parts[:, 1], eps)) * gn,
                                     eps)


def ssd_intra_chunk(cum, b, c, xdt):
    """Mamba2 SSD intra-chunk block: (y (G,Q,P), state (G,N,P),
    chunk_decay (G,)) from the flattened (G, Q, ...) chunk layout."""
    return on_tpu(lambda: ssd_intra_chunk_pallas(cum, b, c, xdt),
                  lambda: ref.ssd_intra_chunk_ref(cum, b, c, xdt))


def swa_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                  window: Optional[int] = None, causal: bool = True,
                  block_q: int = 128, block_k: int = 128) -> jnp.ndarray:
    """Flash attention with sliding window. (B,T,H,D)/(B,S,Hkv,D) layout;
    GQA: kv heads are repeated to match q heads."""
    b, t, h, d = q.shape
    s, hkv = k.shape[1], k.shape[2]
    if hkv != h:
        rep = h // hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    out = on_tpu(
        lambda: swa_attention_pallas(qf, kf, vf, window=window,
                                     causal=causal, block_q=block_q,
                                     block_k=block_k),
        lambda: ref.swa_attention_ref(qf, kf, vf, window=window,
                                      causal=causal))
    return out.reshape(b, h, t, d).transpose(0, 2, 1, 3)
