"""Fused PAOTA/AirComp aggregation kernel (TPU Pallas).

Computes, in ONE pass over HBM:

    out[d] = ( sum_k bp[k] * stacked[k, d] + noise[d] ) / sum_k bp[k]

where bp = b * p (masked transmit powers). The naive jnp composition makes
four HBM passes (scale, reduce, add-noise, normalize); the paper's hot loop
runs this every aggregation period over the full model vector, so the fused
streaming form is the memory-bound kernel the roofline wants: bytes moved
= K*D + D reads + D writes, arithmetic intensity ~= 1 MAC/element.

``superpose_normalize_pallas`` runs one ``pallas_call`` per leaf of the
round's pytree and reads each leaf in its own layout
(``repro.kernels.tiling``): a leaf of rank >= 3 in (K, br, C) blocks of
about 2 MiB, as K f32 multiply-adds per r-row chunk on the VPU, its
aggregate written straight into the leaf's shape; a rank-2 leaf (a
raveled model, cohort slot rows) in byte-sized (K, block_d) lane stripes,
the reduction over K a (1, K)x(K, block_d) f32 matmul. The host server's
raveled ``aircomp_sum`` (``repro.kernels.ops``) is the same kernel with
the mask all ones.

The leading (client) axis is whatever plane the round carries: all K
clients on the dense path, or the (m, d) active-cohort slot rows of a
cohort carry (``RoundCarry.slot_client`` set) — dead/masked slots
superpose with b*p = 0, so the same kernel serves both layouts unchanged.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.round_stats import out_struct
from repro.kernels.tiling import (LANES, PLANE_BLOCK_BYTES,
                                  SCATTER_BLOCK_BYTES, native_rows,
                                  native_view, per_block, scatter_blocks,
                                  stripe_lanes, sublanes)


# Every superposition contracts f32 weights b_k p_k at full precision. At
# a TPU's default precision the MXU takes f32 operands as one bf16 pass,
# which rounds the weights (and an f32 payload) to 8 mantissa bits.
HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# fused superpose-and-normalize (mask + superposition + AWGN + varsigma in
# one pass, varsigma returned)
# ---------------------------------------------------------------------------

def _raw_varsigma(p_ref, m_ref, k):
    """sum_k b_k p_k from the (K,) powers and mask in SMEM."""
    return jax.lax.fori_loop(0, k, lambda kk, a: a + p_ref[kk] * m_ref[kk],
                             jnp.float32(0.0), unroll=k <= 8)


def _superpose_native_kernel(vs_min, blocks, r, p_ref, m_ref, x_ref,
                             noise_ref, out_ref, vs_ref):
    """One (K, br, C) block of a native-view leaf: K f32 multiply-adds of
    r-row chunks on the VPU, the noise and the normalizer joining per
    chunk. Rows past the leaf's end compute garbage that the write-back
    drops."""
    k, _, _ = x_ref.shape
    l, i = pl.program_id(0), pl.program_id(1)
    raw = _raw_varsigma(p_ref, m_ref, k)
    varsigma = jnp.maximum(raw, vs_min)

    def sweep(valid):
        def rows(j, carry):
            at = pl.ds(pl.multiple_of(j * r, r), r)

            def client(kk, acc):
                bp = p_ref[kk] * m_ref[kk]
                return acc + bp * x_ref[kk, at, :].astype(jnp.float32)

            acc = jax.lax.fori_loop(
                0, k, client, jnp.zeros((r, x_ref.shape[2]), jnp.float32),
                unroll=k <= 8)
            out_ref[at, :] = (acc + noise_ref[at, :]) / varsigma
            return carry

        jax.lax.fori_loop(0, -(-valid // r), rows, 0)

    per_block(blocks, i, sweep)

    @pl.when((l == 0) & (i == 0))
    def _emit_vs():
        vs_ref[0] = raw


def _superpose_stripe_kernel(vs_min, p_ref, m_ref, x_ref, noise_ref,
                             out_ref, vs_ref):
    """One (K, block_d) stripe of a rank-2 leaf: the masked powers as a
    (1, K) row contracted with the stripe on the MXU. Lanes past the
    leaf's end compute garbage that the write-back drops."""
    i = pl.program_id(0)
    bp = p_ref[...] * m_ref[...]                # (1, K) f32, masked in-kernel
    raw = jnp.sum(bp)
    varsigma = jnp.maximum(raw, vs_min)
    # a bf16 payload is widened here: a mixed bf16 x f32 contraction
    # rounds the f32 weights b_k p_k to bf16 on the MXU
    x = x_ref[...].astype(jnp.float32)          # (K, block_d)
    acc = jax.lax.dot_general(
        bp, x, (((1,), (0,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32)     # (1, block_d)
    out_ref[...] = (acc + noise_ref[...]) / varsigma

    @pl.when(i == 0)
    def _emit_vs():
        vs_ref[...] = raw[None, None]


@functools.partial(jax.jit, static_argnames=("vs_min", "block_bytes",
                                             "interpret"))
def superpose_normalize_pallas(stacked: jnp.ndarray, powers: jnp.ndarray,
                               mask: jnp.ndarray, noise: jnp.ndarray, *,
                               vs_min: float = 1e-12,
                               block_bytes: int = PLANE_BLOCK_BYTES,
                               interpret: bool = False):
    """Eqs. (6)+(8) in one sweep: stacked (K, ...) payloads of one leaf,
    powers/mask (K,), noise of the leaf's shape (stacked.shape[1:]) ->
    ``(agg f32 of the leaf's shape, varsigma f32 scalar)`` where

        agg      = (sum_k b_k p_k stacked[k] + noise) / max(varsigma, vs_min)
        varsigma = sum_k b_k p_k                       (raw, unclamped)

    The b*p masking joins the kernel and the eq.-8 normalizer comes back
    with the aggregate, so the zero-uploader guard needs no second
    reduction. ``stacked`` may be
    bf16; products and sums are always f32.

    A leaf of rank >= 3 is read, and its aggregate written, in its own
    layout as (L, S, C) blocks of rows (``repro.kernels.tiling``); a
    rank-2 leaf in lane stripes. ``block_bytes`` sets the bytes of one
    block of the payload plane (tests use small blocks to reach ragged
    tails on small leaves).

    ``interpret=True`` runs the kernel body in the Pallas interpreter
    (tests on hosts without a TPU)."""
    k = stacked.shape[0]
    leaf = stacked.shape[1:]
    noise = noise.astype(jnp.float32).reshape(leaf)
    powers = powers.astype(jnp.float32)
    mask = mask.astype(jnp.float32)
    ops_ = (stacked, powers, mask, noise)
    blocks = None
    if stacked.ndim >= 3:
        _, ll, s, c = native_view(stacked.shape)
        blocks = native_rows(k, s, c, [stacked.dtype],
                             [jnp.float32, jnp.float32], block_bytes)
    if blocks is not None:
        r = max(sublanes(stacked.dtype), sublanes(jnp.float32))
        row = pl.BlockSpec((None, blocks.size, c), lambda li, i: (li, i, 0))
        smem = pl.BlockSpec(memory_space=pltpu.SMEM)
        agg, vs = pl.pallas_call(
            functools.partial(_superpose_native_kernel, float(vs_min),
                              blocks, r),
            grid=(ll, blocks.count),
            in_specs=[smem, smem,
                      pl.BlockSpec((k, None, blocks.size, c),
                                   lambda li, i: (0, li, i, 0)),
                      row],
            out_specs=[row, smem],
            out_shape=[out_struct((ll, s, c), jnp.float32, *ops_),
                       out_struct((1,), jnp.float32, powers, mask)],
            interpret=interpret,
            name="superpose_normalize_pallas",
        )(powers, mask, stacked.reshape((k, ll, s, c)),
          noise.reshape((ll, s, c)))
        return agg.reshape(leaf), vs[0]
    x2 = stacked.reshape((k, -1))
    n = x2.shape[1]
    blocks = stripe_lanes(k, n, [stacked.dtype], [jnp.float32, jnp.float32],
                          block_bytes, weight_rows=2)
    stripe = lambda i: (0, i)
    row = pl.BlockSpec((1, k), lambda i: (0, 0))
    agg, vs = pl.pallas_call(
        functools.partial(_superpose_stripe_kernel, float(vs_min)),
        grid=(blocks.count,),
        in_specs=[row, row,                                  # powers, mask
                  pl.BlockSpec((k, blocks.size), stripe),    # payload
                  pl.BlockSpec((1, blocks.size), stripe)],   # noise
        out_specs=[pl.BlockSpec((1, blocks.size), stripe),
                   pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_shape=[out_struct((1, n), jnp.float32, *ops_),
                   out_struct((1, 1), jnp.float32, powers, mask)],
        interpret=interpret,
        name="superpose_normalize_pallas",
    )(powers[None, :], mask[None, :], x2, noise.reshape((1, n)))
    return agg.reshape(leaf), vs[0, 0]


# ---------------------------------------------------------------------------
# shard-aware entry point (mesh client axis)
# ---------------------------------------------------------------------------

def aircomp_sum_psum(stacked: jnp.ndarray, bp: jnp.ndarray,
                     noise: jnp.ndarray, axis_name,
                     varsigma_min: float | None = None):
    """AirComp reduction for use INSIDE ``jax.shard_map`` with the K axis
    laid over mesh client axis/axes ``axis_name``.

    stacked: (K_local, D) this shard's client payloads; bp: (K_local,)
    masked transmit powers b_k p_k; noise: (D,) the SAME AWGN realization
    on every shard (replicated key — eq. 6 adds noise once at the server,
    not per client).

    The local partial superposition is the identical (1, K)x(K, D)
    contraction the single-device Pallas kernel tiles; the cross-shard sum
    is one psum, and the noise joins the accumulator dtype once AFTER the
    collective so every shard normalizes the same received y.

    Returns (aggregate (D,) in f32, varsigma) — both replicated across
    shards. The aggregate is NOT cast back to the payload dtype: a bf16
    carry stores its planes rounded, but the global update must stay
    full precision (same contract as ``superpose_normalize``).
    """
    if varsigma_min is None:
        # the division clamp doubles as the zero-uploader threshold; there
        # is exactly one value of it (lazy import: cycle-free, and keeps
        # this module importable without touching core)
        from repro.core.aircomp import VARSIGMA_MIN
        varsigma_min = VARSIGMA_MIN
    acc = jax.lax.dot_general(
        bp[None, :].astype(jnp.float32), stacked.astype(jnp.float32),
        (((1,), (0,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32)[0]            # (D,) local partial
    acc = jax.lax.psum(acc, axis_name)
    varsigma = jnp.maximum(jax.lax.psum(jnp.sum(bp), axis_name), varsigma_min)
    agg = (acc + noise.astype(acc.dtype)) / varsigma
    return agg, varsigma


def aircomp_sum_tree_psum(stacked_leaves, bp: jnp.ndarray, noise_leaves,
                          axis_name, varsigma_min: float | None = None):
    """AirComp reduction for a params PYTREE inside ``jax.shard_map`` with
    the leading K axis of every leaf laid over mesh client axis/axes.

    stacked_leaves: list of (K_local, ...) leaves (tree_flatten order);
    bp: (K_local,) masked transmit powers b_k p_k; noise_leaves: matching
    per-leaf slices of the SAME flat AWGN realization on every shard
    (``repro.core.aggregation.stacked_tree_noise`` from the replicated key
    — eq. 6 adds noise once at the server, not per client or per leaf).

    One-psum-per-round invariant: each leaf's local superposition partial
    (the same (1, K)x(K, D) contraction the single-leaf entry runs) is
    flattened in f32, all partials are concatenated WITH the local
    varsigma partial appended, and the cross-shard reduction is a single
    psum of that flat vector — never one collective per leaf. Noise joins
    the f32 accumulator once, after the collective, so every shard
    normalizes the same received y.

    Returns (list of (D_leaf...) f32 aggregates, varsigma) — both
    replicated across shards. Aggregates are NOT cast back to the leaf
    dtype: a bf16 carry stores its planes rounded, but the global update
    must stay full precision (same contract as ``superpose_normalize``).
    """
    flat = aircomp_partial_tree(stacked_leaves, bp, axis_name=axis_name)
    return aircomp_finalize_tree(flat, stacked_leaves, noise_leaves,
                                 varsigma_min=varsigma_min)


def aircomp_partial_tree(stacked_leaves, bp: jnp.ndarray, axis_name=None):
    """The local half of ``aircomp_sum_tree_psum``: this shard's flattened
    eq.-6 superposition partial — per-leaf (1, K)x(K, D) f32 contractions
    concatenated with the local varsigma partial (sum of bp) appended,
    one flat (d_total + 1,) f32 vector.

    ``axis_name=None`` returns the purely local partial; a mesh axis
    name/tuple reduces it over that SUBSET of the client axes (e.g. the
    intra-pod axes of grouped aggregation — a per-pod partial that stays
    resident across periods until the cross-pod sync). bp = 0 rows (masked
    or phantom clients) contribute exact zeros, so an all-masked shard's
    partial is bit-exactly zero."""
    bp32 = bp[None, :].astype(jnp.float32)
    parts = [jax.lax.dot_general(
        bp32, leaf.reshape((leaf.shape[0], -1)).astype(jnp.float32),
        (((1,), (0,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32)[0]
        for leaf in stacked_leaves]
    parts.append(jnp.sum(bp).astype(jnp.float32)[None])
    flat = jnp.concatenate(parts)
    if axis_name:
        flat = jax.lax.psum(flat, axis_name)
    return flat


def aircomp_partial_tree_tp(stacked_leaves, bp: jnp.ndarray, tp):
    """The local half of ``aircomp_sum_tree_psum_tp``: this device's
    eq.-6 superposition partial embedded at its position in the FULL
    flattened model vector.

    Each TP-sharded leaf's (1, K)x(K, D_local) contraction lands in a
    full-trailing-shape zero buffer at this shard's TP offset (a
    ``dynamic_update_slice`` along the leaf's TP dim, BEFORE flattening —
    a TP-local block is not a contiguous run of the row-major flat
    vector); TP-replicated leaves and the varsigma partial are masked to
    the lead TP shard so the clients x TP psum counts them exactly once.
    Returns one flat (d_total_FULL + 1,) f32 vector — psumming it over
    the client AND TP axes performs the cross-client superposition and
    the TP gather in the same single collective."""
    from repro.sharding.tp import tp_linear_index, tp_mask_lead

    bp32 = bp[None, :].astype(jnp.float32)
    idx = tp_linear_index(tp)
    parts = []
    for leaf, dim in zip(stacked_leaves, tp.leaf_dims):
        acc = jax.lax.dot_general(
            bp32, leaf.reshape((leaf.shape[0], -1)).astype(jnp.float32),
            (((1,), (0,)), ((), ())), precision=HIGHEST,
            preferred_element_type=jnp.float32)[0]
        trail = leaf.shape[1:]
        acc = acc.reshape(trail)
        if dim >= 0:
            full = list(trail)
            full[dim] *= tp.shards
            starts = [0] * len(trail)
            starts[dim] = idx * trail[dim]
            acc = jax.lax.dynamic_update_slice(
                jnp.zeros(tuple(full), jnp.float32), acc, tuple(starts))
        else:
            acc = tp_mask_lead(acc, tp)
        parts.append(acc.reshape(-1))
    parts.append(tp_mask_lead(jnp.sum(bp).astype(jnp.float32), tp)[None])
    return jnp.concatenate(parts)


def aircomp_sum_tree_psum_tp(stacked_leaves, bp: jnp.ndarray, noise_leaves,
                             axis_name, tp,
                             varsigma_min: float | None = None):
    """``aircomp_sum_tree_psum`` with the model storage TP-sharded inside
    each client shard (``tp``: ``repro.sharding.tp.TPTopology``).

    Keeps the one-psum-per-round invariant: the single model-sized psum
    now spans the client axes AND ``tp.axes`` (one collective; the group
    is the whole mesh), simultaneously superposing across clients and
    gathering across TP shards — after it every device holds the full
    received y. ``noise_leaves`` must be drawn at the FULL leaf shapes
    (``tp_full_structs``) from the replicated round key, exactly as the
    flat program draws them, and join once after the collective — so the
    AWGN realization is a function of the MODEL, not the TP layout, and
    every TP extent consumes the same total noise.

    Returns (list of FULL-shape f32 aggregate leaves, varsigma), both
    replicated over every mesh axis."""
    from repro.sharding.tp import tp_full_structs

    flat = aircomp_partial_tree_tp(stacked_leaves, bp, tp)
    axes = axis_name if isinstance(axis_name, (tuple, list)) else (axis_name,)
    flat = jax.lax.psum(flat, tuple(axes) + tuple(tp.axes))
    return aircomp_finalize_tree(flat, tp_full_structs(stacked_leaves, tp),
                                 noise_leaves, varsigma_min=varsigma_min)


# ---------------------------------------------------------------------------
# gather-superpose-decompress: AirComp over the (m, s) compressed cohort
# plane without ever materializing the dense (m, d) payload
# ---------------------------------------------------------------------------

def _gather_superpose_kernel(vs_min, blocks, m, s, scaled, *refs):
    """One (hb, 128) stripe of the aggregate against one (bm, bs) block
    of the slot plane. Each slot row's elements e, on lanes, scatter as
    one MXU matmul (hb, bs) x (128, bs)^T: the row one-hot carries the
    weighted value a_e = w_row * v_e where the stripe's row matches
    idx_e // 128, the lane one-hot is 1 where the lane matches
    idx_e % 128. Lanes past the plane's end are zeroed; rows past it are
    never read."""
    if scaled:
        bp_ref, sc_ref, val_ref, idx_ref, noise_ref, out_ref, vs_ref, \
            v32_ref = refs
    else:
        bp_ref, val_ref, idx_ref, noise_ref, out_ref, vs_ref, v32_ref = refs
    t, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    hb, bs = out_ref.shape[0], val_ref.shape[1]
    last = (i == blocks.rows.count - 1) & (j == blocks.lanes.count - 1)

    @pl.when((i == 0) & (j == 0))
    def _init():
        out_ref[...] = noise_ref[...]

    # the block widened to f32 once (a row of a packed bf16 or int8 block
    # cannot be loaded at a dynamic offset); lanes past s hold whatever
    # VMEM held, indices that may match a stripe row, so they are zeroed
    v = val_ref[...].astype(jnp.float32)
    if blocks.lanes.tail < bs:
        v = jnp.where(j * bs + jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
                      < s, v, 0.0)
    v32_ref[...] = v
    tile_row = t * hb + jax.lax.broadcasted_iota(jnp.int32, (hb, bs), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (LANES, bs), 0)

    def block(valid_rows):
        def row(r, carry):
            k = i * blocks.rows.size + r
            # w folds b*p (masked) and any int8 dequantization scale, so
            # dead and masked slots contribute exact zeros
            w = bp_ref[k] * sc_ref[k] if scaled else bp_ref[k]
            a = w * v32_ref[pl.ds(r, 1), :]                      # (1, bs)
            e = idx_ref[pl.ds(r, 1), :]
            rows_oh = jnp.where(tile_row == e // LANES, a, 0.0)
            lanes_oh = (lane == e % LANES).astype(jnp.float32)
            out_ref[...] += jax.lax.dot_general(
                rows_oh, lanes_oh, (((1,), (1,)), ((), ())),
                precision=HIGHEST, preferred_element_type=jnp.float32)
            return carry

        jax.lax.fori_loop(0, valid_rows, row, 0)

    per_block(blocks.rows, i, block)

    @pl.when(last)
    def _normalize():
        raw = jax.lax.fori_loop(0, m, lambda k, acc: acc + bp_ref[k],
                                jnp.float32(0.0), unroll=m <= 8)
        out_ref[...] = out_ref[...] / jnp.maximum(raw, vs_min)

        @pl.when(t == 0)
        def _emit_vs():
            vs_ref[0] = raw


@functools.partial(jax.jit, static_argnames=("d", "vs_min", "block_bytes",
                                             "interpret"))
def gather_superpose_pallas(values: jnp.ndarray, idx: jnp.ndarray,
                            bp: jnp.ndarray, noise: jnp.ndarray, *, d: int,
                            scale: jnp.ndarray | None = None,
                            vs_min: float = 1e-12,
                            block_bytes: int = SCATTER_BLOCK_BYTES,
                            interpret: bool = False):
    """AirComp over compressed cohort rows, fused: slot gather + b*p
    masking + compressed superposition + AWGN + varsigma in one pass.

    values: (m, s) compressed payload rows (f32 / bf16 / int8);
    idx: (m, s) int32 support (each row's coordinates in [0, d));
    bp: (m,) masked transmit powers b_k p_k; noise: (d,) AWGN;
    scale: optional (m,) int8 dequantization factors, folded into the
    per-row weight so the stored int8 plane is read as it is, with f32
    products and accumulation — varsigma stays the RAW sum of b*p.

    The aggregate is built as an (H, 128) tile, H = cdiv(d, 128): each
    index splits into a row idx // 128 and a lane idx % 128, and a slot
    row's scatter is the matmul of its row one-hot, weighted, with its
    lane one-hot — (hb, bs) x (128, bs)^T at HIGHEST on the MXU, m*s*(hb
    + 128) one-hot elements per stripe where a one-hot over d lanes
    would take m*s*d. Grid: (stripes of hb aggregate rows) x (blocks of
    slot rows) x (blocks of slot lanes), sized from ``block_bytes``
    (``repro.kernels.tiling.scatter_blocks``); the values, indices and
    (m,) weights are read in their own layouts. Each stripe starts from
    its noise and is normalized on its last block — the dense (m, d)
    plane never exists. Returns ((d,) f32 aggregate, raw varsigma).

    ``interpret=True`` runs the kernel body in the Pallas interpreter
    (tests on hosts without a TPU)."""
    m, s = values.shape
    h = -(-d // LANES)
    blocks = scatter_blocks(m, s, h, values.dtype, block_bytes)
    bp32 = bp.astype(jnp.float32)
    weights = [bp32] if scale is None else [bp32, scale.astype(jnp.float32)]
    noise = jnp.pad(noise.astype(jnp.float32),
                    (0, h * LANES - d)).reshape((h, LANES))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    slots = pl.BlockSpec((blocks.rows.size, blocks.lanes.size),
                         lambda t, i, j: (i, j))
    stripe = pl.BlockSpec((blocks.tiles.size, LANES), lambda t, i, j: (t, 0))
    agg, vs = pl.pallas_call(
        functools.partial(_gather_superpose_kernel, float(vs_min), blocks,
                          m, s, scale is not None),
        grid=(blocks.tiles.count, blocks.rows.count, blocks.lanes.count),
        in_specs=[smem] * len(weights) + [slots, slots, stripe],
        out_specs=[stripe, smem],
        out_shape=[out_struct((h, LANES), jnp.float32, values, idx, noise,
                              *weights),
                   out_struct((1,), jnp.float32, bp32)],
        scratch_shapes=[pltpu.VMEM((blocks.rows.size, blocks.lanes.size),
                                   jnp.float32)],
        interpret=interpret,
        name="gather_superpose_pallas",
    )(*weights, values, idx.astype(jnp.int32), noise)
    return agg.reshape(-1)[:d], vs[0]


def gather_superpose_psum(values: jnp.ndarray, idx: jnp.ndarray,
                          bp: jnp.ndarray, noise: jnp.ndarray, axis_name,
                          d: int, scale: jnp.ndarray | None = None,
                          varsigma_min: float | None = None):
    """Compressed-cohort AirComp INSIDE ``jax.shard_map`` with the slot
    axis laid over mesh client axis/axes ``axis_name``: this shard's
    (m_local, s) rows scatter to d-space and contract locally, the local
    aggregate partial and varsigma partial cross shards as ONE flat psum
    (the one-psum-per-round invariant), and the shared AWGN joins the f32
    accumulator once after the collective. ``scale`` folds int8
    dequantization into the contraction weights; varsigma sums RAW b*p.

    Returns ((d,) f32 aggregate, clamped varsigma), replicated."""
    if varsigma_min is None:
        from repro.core.aircomp import VARSIGMA_MIN
        varsigma_min = VARSIGMA_MIN
    m = values.shape[0]
    bp32 = bp.astype(jnp.float32)
    w = bp32 if scale is None else bp32 * scale.astype(jnp.float32)
    rows = jnp.arange(m)[:, None]
    dense = jnp.zeros((m, d), jnp.float32).at[rows, idx].add(
        values.astype(jnp.float32))
    acc = jax.lax.dot_general(
        w[None, :], dense, (((1,), (0,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32)[0]               # (d,) partial
    flat = jnp.concatenate([acc, jnp.sum(bp32)[None]])
    flat = jax.lax.psum(flat, axis_name)
    varsigma = jnp.maximum(flat[-1], varsigma_min)
    agg = (flat[:-1] + noise.astype(jnp.float32)) / varsigma
    return agg, varsigma


def aircomp_finalize_tree(flat: jnp.ndarray, stacked_leaves, noise_leaves,
                          axis_name=None, varsigma_min: float | None = None):
    """The finishing half of ``aircomp_sum_tree_psum``: from the flat
    (d_total + 1,) superposition partial, optionally run the final psum
    over the remaining client axes (the ONE cross-shard — or cross-pod —
    collective), then clamp varsigma, split per leaf, and add the shared
    AWGN once in f32 before normalizing. ``stacked_leaves`` only supplies
    the leaf shapes for the split.

    Returns (list of f32 aggregate leaves, varsigma) — replicated over
    every axis the partial was reduced over."""
    if varsigma_min is None:
        from repro.core.aircomp import VARSIGMA_MIN
        varsigma_min = VARSIGMA_MIN
    if axis_name:
        flat = jax.lax.psum(flat, axis_name)
    varsigma = jnp.maximum(flat[-1], varsigma_min)
    out, off = [], 0
    for leaf, noise in zip(stacked_leaves, noise_leaves):
        size = int(np.prod(leaf.shape[1:]))
        acc = flat[off:off + size]
        off += size
        agg = (acc + noise.reshape(-1).astype(acc.dtype)) / varsigma
        out.append(agg.reshape(leaf.shape[1:]))
    return out, varsigma
