"""Fused round-stats kernel (TPU Pallas) + the jnp twin.

Everything the PAOTA round's eq.-25 stage needs from the (K, d) delta
plane — per-client dots with the global direction, per-client delta
sq-norms, optionally per-client payload sq-norms for the power constraint
(7), and the global-direction sq-norm — computed in ONE tiled sweep:

    dot_k  = sum_d deltas[k, d] * g[d]
    dn2_k  = sum_d deltas[k, d]^2
    pn2_k  = sum_d payload[k, d]^2        (payload pass only)
    gn2    = sum_d g[d]^2

The naive composition (``client_dots`` + ``client_sq_norms`` +
``client_sq_norms(payload)`` + ``global_sq_norm``) sweeps the K x d plane
three times and the d vector twice; at transformer-scale d the round is
memory-bound, so the fused form is the difference between one and three
HBM passes per aggregation period.

Two implementations, same contract:

* ``round_stats_pallas`` — the TPU kernel, one ``pallas_call`` per leaf.
  A leaf of rank >= 3 is read in its own layout, in (K, br, C) blocks of
  about 2 MiB (``repro.kernels.tiling``): per client, f32 elementwise
  products summed into (8, C) vreg accumulators over r-row chunks, the
  per-block totals added into a revisited (K, ncol) output accumulator
  (like ``cosine_sim``). A rank-2 leaf (the raveled (K, D) plane) is read
  in byte-sized (K, block_d) lane stripes. A ragged last block is masked
  in the kernel. Inputs may be bf16; products and sums are always f32.
* ``round_stats_jnp`` — the CPU/GPU twin: the dot is a matmul and each
  sq-norm is a batched dot (``einsum kd,kd->k``) so NOTHING K x d ever
  materializes (XLA-CPU lowers ``sum(x*x, -1)`` as a full materialized
  square + reduce-window cascade — two extra plane sweeps per norm; the
  batched dot streams once). An explicitly d-chunked ``lax.scan`` variant
  (``chunk=``) exists for experimentation, but measured inside the
  scanned round XLA's own fusion of the plain ops wins (dot operands
  materialize per chunk), so the round core uses ``chunk=None``.

``repro.kernels.ops.round_stats`` picks between them by the platform the
computation is lowered for;
``repro.kernels.ref.round_stats_ref`` is the allclose oracle.

The leading axis is whatever client plane the round carries: the dense
(K, d) delta stack, or — in active-cohort mode (``RoundCfg.cohort_size``)
— the (m, d) cohort slot rows, m = |in-flight cohort| << K. The kernel is
shape-agnostic there; masked slot rows arrive with ``stal = 0`` exactly
like the sharded drivers' phantom clients.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiling import (PLANE_BLOCK_BYTES, native_rows, native_view,
                                  per_block, stripe_lanes, sublanes)

# d-chunk of the explicitly-chunked jnp variant. Leaves at or below this
# size reduce in one shot with the historical ops (bit-identical
# small-model trajectories); ``round_stats_jnp(chunk=...)`` can stream
# larger leaves in CHUNK_D slices. The round core's default is chunk=None
# (no explicit chunking): measured inside the scanned round, XLA's own
# multi-output loop fusion of the plain reductions beats a hand-rolled
# lax.scan whose dot operands must materialize per chunk — the explicit
# form is kept for the kernel tests and for experimentation.
CHUNK_D = 8192
# The stats contract f32 rows at full precision: at a TPU's default
# precision an f32 dot is one bf16 pass (8 mantissa bits).
HIGHEST = jax.lax.Precision.HIGHEST


def out_struct(shape, dtype, *operands):
    """A kernel output's type: varying over the mesh axes its operands
    vary over (inside ``jax.shard_map``, whose replication check needs the
    kernel's outputs typed), invariant outside one."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


# ---------------------------------------------------------------------------
# TPU kernel
# ---------------------------------------------------------------------------

def _fold(x):
    """An (r, C) chunk summed over its 8-row groups: (8, C). The groups
    are whole vregs, so the fold is vreg adds."""
    out = x[:8]
    for q in range(8, x.shape[0], 8):
        out = out + x[q:q + 8]
    return out


def _total(acc):
    """(8, C) partial sums -> (1, 1)."""
    return jnp.sum(jnp.sum(acc, axis=1, keepdims=True), axis=0,
                   keepdims=True)


def _row_sums(valid: int, r: int, c: int, step, n_acc: int):
    """``step(j, accs, masked)`` over the r-row chunks of a block's
    ``valid`` rows, with ``n_acc`` (8, C) f32 accumulators in vregs: full
    chunks in a loop, then one masked partial chunk. Returns the (1, 1)
    totals."""
    full, rem = divmod(valid, r)
    zero = jnp.zeros((8, c), jnp.float32)
    accs = jax.lax.fori_loop(0, full, lambda j, a: step(j, a, False),
                             (zero,) * n_acc)
    if rem:
        accs = step(full, accs, True)
    return [_total(a) for a in accs]


def _native_kernel(blocks, r, d_ref, g_ref, *refs):
    """One (K, br, C) block of a native-view leaf: per client, f32
    elementwise products of the delta (and payload) rows and the
    direction, summed into (8, C) vreg accumulators chunk by chunk; the
    (K, ncol) totals join the revisited output accumulator."""
    p_ref = refs[0] if len(refs) == 3 else None
    out_ref, gn2_ref = refs[-2:]
    k, _, c = d_ref.shape
    ncol = out_ref.shape[1]
    l, i = pl.program_id(0), pl.program_id(1)

    @pl.when((l == 0) & (i == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)
        gn2_ref[...] = jnp.zeros_like(gn2_ref)

    def sweep(valid):
        rem = valid % r

        def chunk(ref, j, masked, *row):
            x = ref[(*row, pl.ds(pl.multiple_of(j * r, r), r),
                     slice(None))].astype(jnp.float32)
            if masked:       # rows past the leaf's end hold garbage
                keep = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0) < rem
                x = jnp.where(keep, x, 0.0)
            return x

        def client(kk, partial):
            def step(j, accs, masked):
                g = chunk(g_ref, j, masked)
                x = chunk(d_ref, j, masked, kk)
                out = (accs[0] + _fold(x * g), accs[1] + _fold(x * x))
                if p_ref is not None:
                    p = chunk(p_ref, j, masked, kk)
                    out += (accs[2] + _fold(p * p),)
                return out

            rows = jax.lax.broadcasted_iota(jnp.int32, (k, ncol), 0)
            cols = jax.lax.broadcasted_iota(jnp.int32, (k, ncol), 1)
            for col, v in enumerate(_row_sums(valid, r, c, step, ncol)):
                partial = jnp.where((rows == kk) & (cols == col), v, partial)
            return partial

        partial = jax.lax.fori_loop(0, k, client,
                                    jnp.zeros((k, ncol), jnp.float32),
                                    unroll=k <= 8)
        gn2 = _row_sums(valid, r, c,
                        lambda j, a, m: (a[0] + _fold(
                            chunk(g_ref, j, m) ** 2),), 1)[0]
        out_ref[...] += partial
        gn2_ref[...] += gn2

    per_block(blocks, i, sweep)


def _stripe_kernel(blocks, d_ref, g_ref, *refs):
    """One (K, block_d) stripe of a rank-2 leaf: f32 elementwise products
    reduced over the lanes; lanes past the leaf's end are masked."""
    p_ref = refs[0] if len(refs) == 3 else None
    out_ref, gn2_ref = refs[-2:]
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)
        gn2_ref[...] = jnp.zeros_like(gn2_ref)

    def sweep(valid):
        def load(ref):
            x = ref[...].astype(jnp.float32)
            if valid < x.shape[1]:
                keep = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) < valid
                x = jnp.where(keep, x, 0.0)
            return x

        x, g = load(d_ref), load(g_ref)              # (K, bd), (1, bd)
        cols = [jnp.sum(x * g, axis=1, keepdims=True),
                jnp.sum(x * x, axis=1, keepdims=True)]
        if p_ref is not None:
            p = load(p_ref)
            cols.append(jnp.sum(p * p, axis=1, keepdims=True))
        out_ref[...] += jnp.concatenate(cols, axis=1)
        gn2_ref[...] += jnp.sum(g * g, axis=1, keepdims=True)

    per_block(blocks, i, sweep)


@functools.partial(jax.jit, static_argnames=("block_bytes", "interpret"))
def round_stats_pallas(deltas: jnp.ndarray, g: jnp.ndarray,
                       payload: jnp.ndarray | None = None, *,
                       block_bytes: int = PLANE_BLOCK_BYTES,
                       interpret: bool = False):
    """deltas: (K, ...) one client-stacked leaf; g: the leaf's
    (deltas.shape[1:]) direction; payload: optional, like deltas.

    Returns ``(stats, gn2)`` where stats is (K, 2) ``[dot_k, dn2_k]`` (or
    (K, 3) with ``pn2_k`` appended when ``payload`` is given) and gn2 is
    the f32 scalar ``||g||^2`` — one streaming pass over every operand.

    A leaf of rank >= 3 is read in its own layout, as (K, L, S, C) blocks
    of rows (``repro.kernels.tiling``); a rank-2 leaf in lane stripes.
    ``block_bytes`` sets the bytes of one block of one plane (tests use
    small blocks to reach ragged tails on small leaves).
    """
    k = deltas.shape[0]
    g = g.reshape(deltas.shape[1:])
    planes = (deltas,) if payload is None else (deltas, payload)
    ncol = 1 + len(planes)
    pdt = [x.dtype for x in planes]
    blocks = None
    if deltas.ndim >= 3:
        _, ll, s, c = native_view(deltas.shape)
        blocks = native_rows(k, s, c, pdt, [g.dtype], block_bytes)
    if blocks is not None:
        r = max(sublanes(dt) for dt in (*pdt, g.dtype))
        view = lambda x: x.reshape((k, ll, s, c))
        plane = pl.BlockSpec((k, None, blocks.size, c),
                             lambda li, i: (0, li, i, 0))
        in_specs = [plane, pl.BlockSpec((None, blocks.size, c),
                                        lambda li, i: (li, i, 0))]
        args = [view(deltas), g.reshape((ll, s, c))]
        kern = functools.partial(_native_kernel, blocks, r)
        grid = (ll, blocks.count)
        acc = lambda li, i: (0, 0)
    else:
        d2 = deltas.reshape((k, -1))
        n = d2.shape[1]
        blocks = stripe_lanes(k, n, pdt, [g.dtype], block_bytes)
        view = lambda x: x.reshape((k, n))
        plane = pl.BlockSpec((k, blocks.size), lambda i: (0, i))
        in_specs = [plane, pl.BlockSpec((1, blocks.size), lambda i: (0, i))]
        args = [d2, g.reshape((1, n))]
        kern = functools.partial(_stripe_kernel, blocks)
        grid = (blocks.count,)
        acc = lambda i: (0, 0)
    if payload is not None:
        in_specs.append(plane)
        args.append(view(payload))
    stats, gn2 = pl.pallas_call(
        kern, grid=grid, in_specs=in_specs,
        out_specs=[pl.BlockSpec((k, ncol), acc),     # revisited accumulator
                   pl.BlockSpec((1, 1), acc)],
        out_shape=[out_struct((k, ncol), jnp.float32, *planes, g),
                   out_struct((1, 1), jnp.float32, g)],
        interpret=interpret, name="round_stats_pallas",
    )(*args)
    return stats, gn2[0, 0]


# ---------------------------------------------------------------------------
# chunked-jnp twin (CPU/GPU fast path; also the interpret-free fallback)
# ---------------------------------------------------------------------------

def _leaf2d(x):
    return x.reshape((x.shape[0], -1))


def _small_leaf_stats(d2, p2, g1):
    """Single-shot per-leaf stats. The row sq-norms are batched dots
    (``einsum kd,kd->k``), NOT ``sum(x*x, -1)``: XLA-CPU lowers the
    latter as a materialized (K, d) square followed by a reduce-window
    cascade — a full extra HBM write+read of the plane per norm — while
    a batched dot contracts in one streaming pass (this was worth ~2
    plane-sweeps per round at transformer-scale d, see EXPERIMENTS.md
    §Round perf)."""
    d32 = d2.astype(jnp.float32)
    g32 = g1.astype(jnp.float32)
    dot = d32 @ g32
    dn2 = jnp.einsum("kd,kd->k", d32, d32)
    out = (dot, dn2)
    if p2 is not None:
        p32 = p2.astype(jnp.float32)
        out += (jnp.einsum("kd,kd->k", p32, p32),)
    return out + (jnp.sum(g32 * g32),)   # (d,)-sized: reduce is fine


def _chunked_leaf_stats(d2, p2, g1, chunk: int):
    """One lax.scan sweep over full d-chunks (+ a remainder tail): the
    multi-output reduction stays in cache per chunk instead of re-reading
    the leaf once per statistic. ``gn2`` reduces outside the scan — it
    only sweeps the (d,) direction vector (negligible traffic), and a
    scalar scan carry seeded from a constant trips shard_map's
    replication checker (constant = replicated, accumulated = shard-
    tagged)."""
    k, n = d2.shape
    n_full = n // chunk
    has_payload = p2 is not None

    def body(carry, i):
        off = i * chunk
        dc = jax.lax.dynamic_slice(d2, (0, off), (k, chunk)).astype(
            jnp.float32)
        gc = jax.lax.dynamic_slice(g1, (off,), (chunk,)).astype(jnp.float32)
        dot, dn2, pn2 = carry
        dot = dot + dc @ gc
        dn2 = dn2 + jnp.einsum("kd,kd->k", dc, dc)
        if has_payload:
            pc = jax.lax.dynamic_slice(p2, (0, off), (k, chunk)).astype(
                jnp.float32)
            pn2 = pn2 + jnp.einsum("kd,kd->k", pc, pc)
        return (dot, dn2, pn2), None

    z = jnp.zeros((k,), jnp.float32)
    (dot, dn2, pn2), _ = jax.lax.scan(body, (z, z, z), jnp.arange(n_full))
    tail = n - n_full * chunk
    if tail:
        dt = d2[:, n_full * chunk:].astype(jnp.float32)
        gt = g1[n_full * chunk:].astype(jnp.float32)
        dot = dot + dt @ gt
        dn2 = dn2 + jnp.einsum("kd,kd->k", dt, dt)
        if has_payload:
            pt = p2[:, n_full * chunk:].astype(jnp.float32)
            pn2 = pn2 + jnp.einsum("kd,kd->k", pt, pt)
    g32 = g1.astype(jnp.float32)
    gn2 = jnp.sum(g32 * g32)
    out = (dot, dn2)
    if has_payload:
        out += (pn2,)
    return out + (gn2,)


def _leaf_stats(dl, plf, gl, chunk):
    d2, g1 = _leaf2d(dl), gl.reshape(-1)
    p2 = None if plf is None else _leaf2d(plf)
    if chunk is None or d2.shape[1] <= chunk:
        return _small_leaf_stats(d2, p2, g1)
    return _chunked_leaf_stats(d2, p2, g1, chunk)


def round_stats_jnp(deltas, g, payload=None, *, chunk: int | None = None):
    """Pytree-generic fused round stats, pure jnp.

    ``deltas``: pytree of client-stacked (K, ...) leaves (a bare (K, D)
    matrix is the raveled single-leaf case); ``g``: the matching global-
    direction pytree / (D,) vector; ``payload``: optional pytree congruent
    with ``deltas`` whose per-client sq-norms are wanted too.

    Returns ``(dots, dn2, pn2 | None, gn2)`` — (K,) f32 vectors plus the
    f32 scalar ``||g||^2`` — accumulated across leaves in tree_flatten
    order (shard-local under a client mesh axis: every reduction runs
    over the model dims, which each shard holds whole).
    """
    d_leaves = jax.tree_util.tree_leaves(deltas)
    g_leaves = jax.tree_util.tree_leaves(g)
    p_leaves = (jax.tree_util.tree_leaves(payload) if payload is not None
                else [None] * len(d_leaves))
    dots = dn2 = pn2 = gn2 = None
    for dl, plf, gl in zip(d_leaves, p_leaves, g_leaves):
        part = _leaf_stats(dl, plf, gl, chunk)
        if dots is None:
            dots, dn2 = part[0], part[1]
            pn2 = part[2] if payload is not None else None
            gn2 = part[-1]
        else:
            dots, dn2 = dots + part[0], dn2 + part[1]
            if payload is not None:
                pn2 = pn2 + part[2]
            gn2 = gn2 + part[-1]
    return dots, dn2, pn2, gn2


# ---------------------------------------------------------------------------
# compressed-plane stats: the sweep over (m, s) rows + EF residuals
# ---------------------------------------------------------------------------

def compressed_round_stats(values, idx, resid, resid_idx, g,
                           scale=None):
    """Round stats over the compressed cohort plane: (m, s) transmitted
    values on per-row supports ``idx``, plus the (m, s) error-feedback
    residuals on their own supports — so eq. 25's similarity factor sees
    each slot's full reconstruction ``scatter(v) + scatter(e)`` without a
    dense (m, d) plane ever materializing:

        dot_k = <v_k, g[idx_k]> + <e_k, g[eidx_k]>
        dn2_k = ||v_k||^2 + ||e_k||^2
        pn2_k = ||v_k||^2      (the TRANSMITTED energy — what the power
                                constraint (7) actually caps on the air)
        gn2   = ||g||^2

    ``scale`` dequantizes int8 values ((m,) per-row factors). Pure jnp on
    every backend: the sweep is gather-bound (O(m*s) with random access
    into g), with no K x d contraction for a Pallas stripe kernel to win
    on — raveled single-leaf only, like the compressed plane itself.
    Returns ``(dots, dn2, pn2, gn2)``, all f32."""
    g32 = g.reshape(-1).astype(jnp.float32)
    v32 = values.astype(jnp.float32)
    if scale is not None:
        v32 = v32 * scale.astype(jnp.float32)[:, None]
    dots = jnp.einsum("ms,ms->m", v32, g32[idx], precision=HIGHEST)
    pn2 = jnp.einsum("ms,ms->m", v32, v32, precision=HIGHEST)
    dn2 = pn2
    if resid is not None:
        r32 = resid.astype(jnp.float32)
        dots = dots + jnp.einsum("ms,ms->m", r32, g32[resid_idx],
                                 precision=HIGHEST)
        dn2 = dn2 + jnp.einsum("ms,ms->m", r32, r32, precision=HIGHEST)
    return dots, dn2, pn2, jnp.sum(g32 * g32)


def round_stats_tp(deltas, g, payload, tp, stats_fn):
    """Intra-client-TP round stats: one psum over the TP axes.

    The stacked leaves of ``deltas``/``payload`` are this device's
    TP-local blocks (trailing dim ``tp.leaf_dims[i]`` holds 1/``shards``
    of the model) while ``g`` is the full replicated global direction —
    so the sweep slices ``g`` down to the matching block per sharded
    leaf, runs ``stats_fn`` (the platform-dispatched dense sweep) over the
    sharded and TP-replicated leaf groups separately, and reduces ONE
    concatenated ``[dots | dn2 (| pn2) | gn2]`` vector over ``tp.axes``.
    TP-replicated leaves (no dividing trailing dim) are accumulated
    OUTSIDE that psum so they count exactly once. With every leaf in one
    group the other contributes exact zeros — same totals either way."""
    from repro.sharding.tp import tp_slice

    d_leaves = jax.tree_util.tree_leaves(deltas)
    g_leaves = jax.tree_util.tree_leaves(g)
    have_p = payload is not None
    p_leaves = (jax.tree_util.tree_leaves(payload) if have_p
                else [None] * len(d_leaves))
    k = d_leaves[0].shape[0]

    sh = ([], [], [])   # sharded leaves: (deltas, g-local, payload)
    rep = ([], [], [])  # TP-replicated leaves
    for dl, gl, plf, dim in zip(d_leaves, g_leaves, p_leaves, tp.leaf_dims):
        dst = sh if dim >= 0 else rep
        dst[0].append(dl)
        dst[1].append(tp_slice(gl, dim, tp) if dim >= 0 else gl)
        dst[2].append(plf)

    def run(group):
        return stats_fn(group[0], group[1], group[2] if have_p else None)

    if sh[0]:
        dots, dn2, pn2, gn2 = run(sh)
    else:
        dots = dn2 = jnp.zeros((k,), jnp.float32)
        pn2 = jnp.zeros((k,), jnp.float32) if have_p else None
        gn2 = jnp.float32(0.0)
    parts = [dots, dn2] + ([pn2] if have_p else []) + [jnp.reshape(gn2, (1,))]
    flat = jax.lax.psum(jnp.concatenate(parts), tp.axes)
    dots, dn2 = flat[:k], flat[k:2 * k]
    off = 2 * k
    if have_p:
        pn2 = flat[off:off + k]
        off += k
    gn2 = flat[off]
    if rep[0]:
        r_dots, r_dn2, r_pn2, r_gn2 = run(rep)
        dots, dn2, gn2 = dots + r_dots, dn2 + r_dn2, gn2 + r_gn2
        if have_p:
            pn2 = pn2 + r_pn2
    return dots, dn2, (pn2 if have_p else None), gn2
