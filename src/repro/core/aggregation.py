"""PAOTA aggregation — the paper's round update (eq. 8/9) in two forms:

1. ``paota_aggregate_stacked``: the FL-simulator form. Client models stacked
   along a leading K axis — either one raveled (K, D) matrix or an arbitrary
   params pytree of (K, ...) leaves. The weighted superposition + channel
   noise + normalization run per leaf with ONE flat AWGN realization for the
   whole model (drawn once from ``key`` and split across leaves in
   tree_flatten order), so the pytree and raveled forms of the same model
   consume bit-identical noise. The single-(K, D)-leaf case is the exact
   historical op sequence (optionally via the Pallas ``aircomp_sum`` kernel).

2. ``paota_allreduce``: the datacenter/shard_map form. Each device group on
   the client mesh axis holds ONE client's payload; the AirComp superposition
   becomes a masked weighted ``psum`` over that axis with AWGN injected after
   normalization — the TPU-native realization of the wireless MAC
   (DESIGN.md §3). Used by repro.launch.train's PAOTA round step.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

from repro.core.aircomp import VARSIGMA_MIN, aircomp_aggregate


def ravel(params) -> Tuple[jnp.ndarray, callable]:
    return ravel_pytree(params)


def guarded_global_update(global_vec, prev_global, agg, varsigma, *,
                          delta: bool = False,
                          threshold: float = VARSIGMA_MIN):
    """Apply the round update with the zero-uploader guard (masked select).

    When the eq.-8 normalizer sum_k b_k p_k sits at/below the clamp, no
    client transmitted this period: `agg` is pure AWGN divided by the
    ~1e-12 clamp, and assigning it would destroy the global model. The
    guard holds both w_g AND prev_global (the gradient-similarity
    direction w_g^t - w_g^{t-1} must not collapse to zero from a skipped
    period). Pure jnp select over every leaf of the (pytree) global — the
    same code path serves the host reference server and the jitted fused
    round; a raveled global is the single-leaf case.

    The same select also guards a NON-FINITE aggregate (a deep-fade round
    whose normalizer survives the clamp but whose payload overflowed, a
    bf16 overflow, an unscreened NaN row): any NaN/Inf anywhere in ``agg``
    holds w_g AND prev_global bit-identically — one poisoned period is a
    skipped period, never a destroyed model. The check is a scalar
    reduction over the (replicated, post-collective) aggregate, so the
    sharded round still compiles to ONE cross-client psum.

    Returns (new_global, new_prev_global)."""
    finite = jnp.bool_(True)
    for leaf in jax.tree_util.tree_leaves(agg):
        finite = finite & jnp.all(jnp.isfinite(leaf))
    has_uploaders = (varsigma > threshold) & finite

    def upd(g, a):
        cand = g + a if delta else a
        return jnp.where(has_uploaders, cand, g)

    return (jax.tree_util.tree_map(upd, global_vec, agg),
            jax.tree_util.tree_map(
                lambda g, pg: jnp.where(has_uploaders, g, pg),
                global_vec, prev_global))


def stacked_tree_noise(key, stacked_leaves, sigma_n):
    """ONE eq.-6 AWGN realization for the whole model: a flat float32 draw
    of the total model size, split per leaf in tree_flatten order (leaf i
    gets the next prod(shape[1:]) entries, shaped to its trailing dims).

    Splitting one flat draw — instead of folding a subkey per leaf — makes
    the noise a function of the MODEL, not of how its params happen to be
    split into leaves: the 4-leaf pytree form of an MLP and its raveled
    (K, D) form consume bit-identical realizations (the single-leaf split
    is exactly the historical ``normal(key, (D,))``), which is what the
    pytree-vs-raveled equivalence tests pin.

    Where the flat draw hashes each element's flat index (threefry,
    partitionable), each leaf's slice is drawn in the leaf's own shape
    (``_normal_at``), bit for bit the same values: on a TPU a slice of a
    flat draw reshaped to a tiled (S, C) leaf is a relayout copy of the
    model-sized f32 noise in front of the superposition kernel. Other key
    types take the flat draw."""
    sizes = [int(np.prod(l.shape[1:])) for l in stacked_leaves]
    if _counter_draw(key, sum(sizes)):
        out, off = [], 0
        for leaf, size in zip(stacked_leaves, sizes):
            out.append(sigma_n * _normal_at(key, off, leaf.shape[1:]))
            off += size
        return out
    flat = sigma_n * jax.random.normal(key, (sum(sizes),), jnp.float32)
    out, off = [], 0
    for leaf, size in zip(stacked_leaves, sizes):
        out.append(flat[off:off + size].reshape(leaf.shape[1:]))
        off += size
    return out


def _counter_draw(key, total: int) -> bool:
    """Whether ``jax.random.normal(key, (total,))`` is the partitionable
    threefry draw, each element a hash of its flat index, that
    ``_normal_at`` reproduces."""
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        impl = str(jax.random.key_impl(key))
    else:
        impl = jax.config.jax_default_prng_impl
    return ("threefry" in impl and jax.config.jax_threefry_partitionable
            and total < 2 ** 32)


def _normal_at(key, offset: int, shape) -> jnp.ndarray:
    """``jax.random.normal(key, (N,), float32)[offset:offset + size]
    .reshape(shape)`` for any N past that slice, bit for bit, drawn in
    ``shape``: the threefry hash of each element's flat index (its high
    word is 0 below 2^32), then ``jax.random.uniform``'s mantissa fill on
    [nextafter(-1, 0), 1) and ``normal``'s sqrt(2) erfinv — the steps of
    ``jax.random.normal``, applied to the slice's counters only."""
    from jax.extend.random import threefry2x32_p
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    count = jnp.full(shape, offset, jnp.uint32)
    for dim in range(len(shape)):
        stride = jnp.uint32(int(np.prod(shape[dim + 1:])))
        count = count + jax.lax.broadcasted_iota(jnp.uint32, shape, dim) * stride
    hi, lo = threefry2x32_p.bind(key[0], key[1], jnp.zeros(shape, jnp.uint32),
                                 count)
    one = np.array(1.0, np.float32).view(np.uint32)
    mant = jax.lax.shift_right_logical(hi ^ lo, jnp.uint32(9)) | one
    floats = jax.lax.bitcast_convert_type(mant, jnp.float32) - 1.0
    lo_f = np.nextafter(np.float32(-1.0), np.float32(0.0))
    u = jax.lax.max(jnp.float32(lo_f),
                    floats * (np.float32(1.0) - lo_f) + lo_f)
    return np.float32(np.sqrt(2)) * jax.lax.erf_inv(u)


def paota_aggregate_stacked(stacked_models, powers: jnp.ndarray,
                            mask: jnp.ndarray, key, sigma_n: float,
                            use_kernel: bool = False, axis_name=None,
                            tp=None):
    """Eq. (8): w_g^{r+1} = (sum_k b_k p_k w_k + n) / sum_k b_k p_k.

    ``stacked_models``: a pytree of client-stacked (K, ...) leaves; the
    raveled federation passes its bare (K, D) matrix (single-leaf pytree)
    and runs the exact historical op sequence. Returns (aggregate pytree /
    (D,) vector, varsigma).

    ``axis_name``: when the K axis is laid over mesh client axis/axes
    inside ``jax.shard_map``, the superposition runs as ONE psum over that
    axis per round — per-leaf local partials are flattened and concatenated
    (``repro.kernels.aircomp_sum.aircomp_sum_tree_psum``), not psum'd leaf
    by leaf — with the single shared noise realization drawn from the
    replicated ``key`` and added once, after the collective: the same
    eq.-6 semantics as the single-device reduction.

    ``tp``: intra-client ``repro.sharding.tp.TPTopology`` when the leaves
    are additionally TP-local model blocks — the single psum then spans
    the client axes AND ``tp.axes`` (superpose + TP-gather in one
    collective), and the AWGN is drawn at the FULL leaf shapes from the
    same replicated key, so the realization is identical across every TP
    layout (the noise-split determinism contract; EXPERIMENTS.md
    §Intra-client TP). Aggregate leaves come back FULL-shape."""
    leaves, treedef = jax.tree_util.tree_flatten(stacked_models)
    single = len(leaves) == 1 and leaves[0].ndim == 2
    bp = powers * mask
    if axis_name is not None:
        from repro.kernels.aircomp_sum import (aircomp_sum_psum,
                                               aircomp_sum_tree_psum,
                                               aircomp_sum_tree_psum_tp)
        if tp is not None:
            from repro.sharding.tp import tp_full_structs
            noise = stacked_tree_noise(key, tp_full_structs(leaves, tp),
                                       sigma_n)
            agg_leaves, varsigma = aircomp_sum_tree_psum_tp(
                leaves, bp, noise, axis_name, tp,
                varsigma_min=VARSIGMA_MIN)
            return (jax.tree_util.tree_unflatten(treedef, agg_leaves),
                    varsigma)
        noise = stacked_tree_noise(key, leaves, sigma_n)
        if single:
            # noise stays f32: the psum entry accumulates f32 and returns
            # an f32 aggregate regardless of payload storage dtype
            agg, varsigma = aircomp_sum_psum(
                leaves[0], bp, noise[0], axis_name,
                varsigma_min=VARSIGMA_MIN)
            return jax.tree_util.tree_unflatten(treedef, [agg]), varsigma
        agg_leaves, varsigma = aircomp_sum_tree_psum(
            leaves, bp, noise, axis_name, varsigma_min=VARSIGMA_MIN)
        return jax.tree_util.tree_unflatten(treedef, agg_leaves), varsigma
    if single and use_kernel:
        return aircomp_aggregate(leaves[0], powers, mask, key, sigma_n,
                                 use_kernel=True)
    varsigma = jnp.maximum(jnp.sum(bp), VARSIGMA_MIN)
    # a STATICALLY zero sigma (noiseless ablation, e.g. the train step's
    # sigma_over_varsigma=0) skips the model-sized AWGN draw entirely —
    # XLA does not fold a float multiply-by-zero away
    noiseless = isinstance(sigma_n, (int, float)) and sigma_n == 0.0
    if noiseless:
        agg = []
        for leaf in leaves:
            l2 = leaf.reshape((leaf.shape[0], -1))
            acc = jnp.einsum("k,kd->d", bp.astype(jnp.float32),
                             l2.astype(jnp.float32),
                             precision=jax.lax.Precision.HIGHEST)
            agg.append((acc / varsigma).reshape(leaf.shape[1:]))
        return jax.tree_util.tree_unflatten(treedef, agg), varsigma
    # fused superpose-and-normalize per leaf (sweep 2 of the round): b*p
    # masking, superposition, AWGN, and the varsigma division in one pass
    # — compiled Pallas kernel on TPU, f32-accumulating einsum elsewhere
    # (repro.kernels.ops.superpose_normalize). Leaves may be bf16; the
    # aggregate always comes back f32 (the globals stay f32).
    from repro.kernels.ops import superpose_normalize
    noise = stacked_tree_noise(key, leaves, sigma_n)
    agg = []
    for leaf, nz in zip(leaves, noise):
        out, _ = superpose_normalize(leaf, powers, mask, nz,
                                     vs_min=VARSIGMA_MIN)
        agg.append(out.reshape(leaf.shape[1:]))
    return jax.tree_util.tree_unflatten(treedef, agg), varsigma


def paota_aggregate_compressed(values, idx, powers: jnp.ndarray,
                               mask: jnp.ndarray, key, sigma_n: float,
                               d: int, scale=None, axis_name=None):
    """Eq. (8) over the (m, s) COMPRESSED cohort plane: each slot's stored
    values on its own support superpose directly into d-space (the
    gather-superpose-decompress kernel — decompression IS the
    superposition, no dense (m, d) plane), with the same flat f32 AWGN
    realization the dense path draws (single-leaf ``stacked_tree_noise``
    == ``sigma_n * normal(key, (d,))``) and the same varsigma clamp.
    ``scale`` folds int8 slot dequantization into the contraction;
    varsigma sums the RAW b*p. Raveled single-leaf only — the compressed
    plane has no pytree form.

    Returns ((d,) f32 aggregate, clamped varsigma); with ``axis_name``
    the slot axis crosses shards as ONE flat psum."""
    bp = powers * mask
    noiseless = isinstance(sigma_n, (int, float)) and sigma_n == 0.0
    noise = (jnp.zeros((d,), jnp.float32) if noiseless
             else sigma_n * jax.random.normal(key, (d,), jnp.float32))
    if axis_name is not None:
        from repro.kernels.aircomp_sum import gather_superpose_psum
        return gather_superpose_psum(values, idx, bp, noise, axis_name, d,
                                     scale=scale, varsigma_min=VARSIGMA_MIN)
    from repro.kernels.ops import gather_superpose
    agg, raw = gather_superpose(values, idx, bp, noise, d=d, scale=scale,
                                vs_min=VARSIGMA_MIN)
    return agg, jnp.maximum(raw, VARSIGMA_MIN)


def paota_partial_stacked(stacked_models, powers: jnp.ndarray,
                          mask: jnp.ndarray, axis_name=None) -> jnp.ndarray:
    """Grouped-aggregation half of eq. (8): the superposition PARTIAL of
    this shard's clients — the flattened per-leaf contractions of
    ``paota_aggregate_stacked`` with the varsigma partial appended, one
    (d_total + 1,) f32 vector — without noise or normalization.

    ``axis_name`` optionally reduces over a SUBSET of the client axes
    (the intra-pod psum that fires every period); the remaining reduction,
    the AWGN, and the eq.-8 division happen once at the window sync
    (``paota_finalize_stacked``). Masked clients (b_k = 0) contribute
    exact zeros, so a pod with no uploaders holds a bit-exactly-zero
    partial."""
    from repro.kernels.aircomp_sum import aircomp_partial_tree
    leaves, _ = jax.tree_util.tree_flatten(stacked_models)
    return aircomp_partial_tree(leaves, powers * mask, axis_name=axis_name)


def paota_finalize_stacked(flat: jnp.ndarray, stacked_models, key,
                           sigma_n: float, axis_name=None):
    """Finish a grouped AirComp window from its accumulated flat partial:
    the final psum over ``axis_name`` (the ONE cross-pod collective of the
    window), then the same single flat AWGN realization
    (``stacked_tree_noise`` — identical draw to the flat path's) joins the
    f32 accumulator once before the varsigma clamp + normalization.
    ``stacked_models`` supplies the leaf shapes only.

    Returns (aggregate pytree / (D,) vector, varsigma) — the exact shapes
    ``paota_aggregate_stacked`` returns, so the round update downstream is
    shared."""
    from repro.kernels.aircomp_sum import aircomp_finalize_tree
    leaves, treedef = jax.tree_util.tree_flatten(stacked_models)
    noise = stacked_tree_noise(key, leaves, sigma_n)
    agg_leaves, varsigma = aircomp_finalize_tree(
        flat, leaves, noise, axis_name=axis_name, varsigma_min=VARSIGMA_MIN)
    return jax.tree_util.tree_unflatten(treedef, agg_leaves), varsigma


def paota_allreduce(local_payload, power: jnp.ndarray, ready: jnp.ndarray,
                    axis_name, noise_key, sigma_n: float):
    """Inside shard_map: each participant holds `local_payload` (pytree),
    scalar `power` (p_k) and `ready` (b_k in {0,1}).

    Returns the PAOTA aggregate, identical on every participant — a weighted
    masked all-reduce with post-normalization AWGN. The noise is generated
    from a shared key so every device injects the SAME realization (one
    channel, one noise draw — matches eq. 6 where noise is added once at the
    server, not per client).
    """
    bp = power * ready
    varsigma = jnp.maximum(jax.lax.psum(bp, axis_name), 1e-12)

    def agg(x):
        s = jax.lax.psum(x * bp.astype(x.dtype), axis_name)
        sub = jax.random.fold_in(noise_key, x.ndim + x.size % 9973)
        noise = sigma_n * jax.random.normal(sub, x.shape, x.dtype)
        return (s + noise) / varsigma.astype(x.dtype)

    return jax.tree_util.tree_map(agg, local_payload)


def exact_average(local_payload, weight: jnp.ndarray, axis_name):
    """Ideal Local SGD aggregation (baseline 1): lossless weighted mean."""
    wsum = jax.lax.psum(weight, axis_name)

    def agg(x):
        return jax.lax.psum(x * weight.astype(x.dtype), axis_name) / wsum.astype(x.dtype)

    return jax.tree_util.tree_map(agg, local_payload)
