"""Functional PAOTA round core — ONE implementation of the aggregation
period, shared by every driver.

The federated model is an arbitrary params PYTREE: every model-sized
quantity (globals, pending local models, their deltas) is carried
leaf-wise, and every cross-model scalar (per-client norms and cosines,
the AirComp superposition, varsigma) is computed as a tree-reduced sum —
per-leaf partials accumulated locally, then reduced ONCE (one psum per
round under sharding, never one per leaf). The raveled federation is the
trivial single-(K, d)-leaf pytree; ``waterfill_beta_jnp`` /
``power_from_beta`` stay shape-agnostic consumers of the reduced (K,)
scalars.

The delta plane is swept exactly TWICE per round (PR 5): the carry holds
the local-update deltas directly (``RoundCarry.deltas`` — the round used
to carry the per-client start models and re-derive ``pending - starts``
every period), so

* sweep 1 — ``repro.kernels.ops.round_stats``: per-client dots with the
  global direction, delta sq-norms, payload sq-norms for the power
  constraint (7), and the global-direction sq-norm, all in one fused pass
  (compiled Pallas kernel on TPU; on CPU the jnp twin's batched-dot
  formulation — never a materialized square — with XLA multi-output
  fusion doing the pass merging);
* sweep 2 — the superpose-and-normalize aggregation (eqs. 6+8), b·p
  masking + superposition + AWGN + varsigma normalization in one pass
  (``repro.kernels.aircomp_sum.superpose_normalize_pallas`` on TPU, the
  f32-accumulating einsum elsewhere; one psum under sharding).

``RoundCfg.pending_dtype`` optionally stores the carry's (K, ...) planes
(pending + deltas) in bf16 — every kernel/reduction accumulates in f32,
the globals stay f32, and the K x d working set halves for giant-model
clients. Deltas are always computed in f32 BEFORE the storage cast
(``trained - w_g``), never as a difference of rounded operands, so the
bf16 error is a relative rounding of the small delta, not a catastrophic
cancellation of two large models.

``paota_round_step`` is the pure round transition (``RoundCarry`` in,
``RoundCarry`` out): scheduler advance -> eq.-25 factors -> water-filling
P2 -> channel + instantaneous cap (7) -> AirComp -> zero-uploader-guarded
update -> broadcast + local train. It is parameterized by

* ``RoundCfg`` — the static problem constants (Theorem-1 c1/c0, channel
  power/noise, the aggregation period, the carry storage dtype), a plain
  NamedTuple of Python scalars closed over at trace time;
* ``RoundStreams`` — the per-driver data/RNG callbacks (local training,
  latency draws, channel draws, the per-round noise key). The callbacks
  are what let the same core run single-device (callbacks see all K
  clients) and mesh-sharded (callbacks see this shard's K/n slice of
  identical global draws);
* ``axis_name`` — ``None`` for the single-device form, or the mesh client
  axis name(s) under ``jax.shard_map``: per-client stages (local SGD,
  factors, channel, power) stay fully parallel — the round stats are
  shard-local by construction (their reductions run over the model dims,
  which every shard holds whole) — and only the AirComp superposition,
  the P2 water-filling reductions, and the round metrics cross shards as
  ``psum``/``pmin``/``pmax`` collectives.

Active-cohort mode (``init_round_carry(..., m=m)``, m >= 1: the carry
then holds ``slot_client``) splits the carry into TWO planes: a dense
(K,) client-state plane — scheduler bits, staleness clocks, and the
vectorized scenario simulator
(``repro.core.scheduler.ScenarioConfig``: availability cycles, dropouts,
lognormal responsiveness), all O(K) scalars advanced inside the scan —
and an (m, ...) active-cohort payload plane holding model-sized rows for
the in-flight cohort only (``slot_client`` / ``slot_live``). Freed slots
refill from the available idle pool by counter-RNG priority. The K x d
carry stops scaling with K: a K = 10^6 federation advances its state
plane on one host while only m payload rows materialize
(benchmarks/cohort_round_bench.py). The same ``paota_round_step`` and
``init_round_carry`` serve both carries: every stage is written once, and
the few places where a slot plane differs (how a row sees the (K,)
state, the P2 row order and channel gather, compressed stats and
superposition, slot turnover with the residual hand-off, the compressed
store) are trace-time branches on the carry's structure, so the dense
program emits none of them.

Consumers: ``repro.fl.fused.FusedPAOTA`` (single device, scan over
rounds, carry donated between scans), ``repro.fl.sharded.ShardedPAOTA``
(the same scan under ``shard_map`` over the mesh client axis), and the
host-path ``repro.fl.server.PAOTAServer`` whose numpy round consumes the
shared stage helpers (``eq25_factors`` / ``constraint7_powers``) so the
three implementations cannot drift apart stage by stage.

Each stage of the round runs under one of ``STAGE_SCOPES``
(``jax.named_scope``), on the dense and the cohort carry alike
(``paota.compress`` only where a cohort compresses its slots), so every
device operation of the compiled scan carries its stage in its
``op_name`` metadata and a profiler trace can be split by stage. The
scopes are metadata only: without them the compiled program differs in
instruction names alone.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.aggregation import (guarded_global_update,
                                    paota_aggregate_compressed,
                                    paota_aggregate_stacked,
                                    paota_finalize_stacked,
                                    paota_partial_stacked)
from repro.core.aircomp import VARSIGMA_MIN, effective_power_cap
from repro.core.boxqp import waterfill_beta_jnp
from repro.core.compress import (dequantize_int8, ef_residual, gather_rows,
                                 quantize_int8_stochastic, scatter_rows,
                                 sparsify, topk_support)
from repro.core.power_control import (client_sq_norms, power_from_beta,
                                      similarity_factor, staleness_factor)
from repro.core.scheduler import sched_advance, sched_broadcast

# The round's stages as named scopes: scheduler advance, broadcast and
# slot turnover; the eq.-25 stats sweep and screening; water-filling and
# the cap (7); superposition, AWGN, the guarded update and rollback; local
# training; a compressed cohort's error feedback, support pick,
# quantisation and residual re-sparsify; the writes of the trained rows
# into the carry's planes.
STAGE_SCOPES = ("paota.schedule", "paota.stats", "paota.power",
                "paota.superpose", "paota.train", "paota.compress",
                "paota.carry_write")


class RoundCarry(NamedTuple):
    """Device-resident PAOTA state threaded through the scan.

    The federated model is an arbitrary params PYTREE: ``global_vec`` /
    ``prev_global`` hold one copy of the model (leaves of the params'
    natural shapes, always f32), ``pending`` / ``deltas`` hold the
    client-stacked form (every leaf with a leading K axis, stored in
    ``RoundCfg.pending_dtype``). ``deltas`` carries ``pending - start``
    directly — the local update each client would transmit — computed in
    f32 at broadcast time; the round never re-derives it from a stored
    start model (one fewer K x d sweep per period, and the bf16 storage
    mode stays a rounding of the small delta instead of a cancellation of
    two large models). The raveled federation is the trivial single-leaf
    instance — a bare (d,) vector / (K, d) matrix.

    Under the sharded driver the ``(K,)`` fields and the leading axis of
    every stacked leaf are laid over the mesh client axis (each shard
    carries its K/n rows); the scalars and the global-model leaves are
    replicated.
    """
    t: jnp.ndarray            # i32 — scheduler round counter
    time: jnp.ndarray         # f32 — simulated clock (seconds, report-only)
    ready: jnp.ndarray        # (K,) bool — b_k at the aggregation slot
    busy_lat: jnp.ndarray     # (K,) f32 — latency draw of each client's
                              # current training session; training-finished
                              # is the exact relative slot predicate
                              # lat <= (t+1 - model_round) * delta_t
                              # (repro.core.scheduler.slot_ready — no
                              # absolute-clock accumulation, so the f32
                              # scan and the host's f64 clock agree
                              # bit-for-bit at any horizon)
    model_round: jnp.ndarray  # (K,) i32 — round each client trains on
    global_vec: jnp.ndarray   # params pytree / (d,) — w_g^t
    prev_global: jnp.ndarray  # params pytree / (d,) — w_g^{t-1} (direction)
    pending: jnp.ndarray      # (K, ...)-leaf pytree — in-flight local models,
                              # or None under transmit='delta' (the round
                              # never reads the full local models there —
                              # the delta plane IS the whole carry, halving
                              # the K x d working set)
    deltas: jnp.ndarray       # (K, ...)-leaf pytree — pending - start model
    held: jnp.ndarray = None  # grouped aggregation only (group_period >= 1):
                              # (n_pod_groups, d_total + 1) f32 — the
                              # staleness-weighted intra-pod superposition
                              # partials (flattened leaf contractions + the
                              # varsigma partial) accumulated since the last
                              # cross-pod sync; sharded over the pod axes,
                              # replicated intra-pod, zeroed at every sync.
                              # None on the flat path.
    slot_client: jnp.ndarray = None  # active-cohort mode only
                              # (init_round_carry's m >= 1): (m,) i32 —
                              # which client occupies each payload slot
                              # (shard-LOCAL row index under sharding). The
                              # (K,) state plane stays dense and tiny;
                              # `pending`/`deltas` shrink to (m, ...) rows
                              # gathered for the in-flight cohort only, so
                              # the K x d carry stops scaling with K. None
                              # on the dense path.
    slot_live: jnp.ndarray = None    # (m,) bool — slot holds a real
                              # in-flight client (False = phantom row:
                              # b_k = 0 through every reduction, exactly the
                              # sharded drivers' phantom-client masking)
    slot_idx: jnp.ndarray = None     # compressed cohort payloads only
                              # (RoundCfg.compress): (m, s) i32 — each
                              # slot's support, the d-space coordinates its
                              # `deltas` values live on (top-k is per-row;
                              # randmask rows trained in different rounds
                              # hold different shared masks, so the support
                              # is per-slot either way). None when off.
    slot_scale: jnp.ndarray = None   # (m,) f32 — int8 slot storage only:
                              # per-row absmax dequantization factors
    slot_resid: jnp.ndarray = None   # (m, s) f32 — error-feedback residual
                              # of each in-flight slot (what the row's
                              # compression dropped), on its own support:
    slot_resid_idx: jnp.ndarray = None  # (m, s) i32. Residuals always f32.
    resid_val: jnp.ndarray = None    # (K, s) f32 — parked EF residuals:
                              # on slot turnover a departing slot scatters
                              # its residual back to the owning client's
                              # row; a re-scheduled client resumes its own
                              # accumulated error. Sharded: (K_local, s).
    resid_idx: jnp.ndarray = None    # (K, s) i32 — parked supports
    good_global: jnp.ndarray = None  # divergence rollback only
                              # (RoundCfg.divergence_factor > 0): params
                              # pytree / (d,) — the last global model that
                              # PASSED the post-update norm check; a
                              # diverged round restores w_g AND prev_global
                              # from this slot (replicated, like the
                              # globals). None when the detector is off.
    good_norm2: jnp.ndarray = None   # f32 scalar — ||good_global||^2,
                              # carried so the check never re-sweeps the
                              # last-good model


class RoundCfg(NamedTuple):
    """Static per-federation constants of the round (Python scalars only —
    closed over at trace time, never traced)."""
    omega: float              # staleness constant Omega (Sec. IV-A)
    c1: float                 # L eps^2 K   (P2 term-d scale)
    c0: float                 # 2 L d sigma_n^2 (P2 term-e numerator)
    p_max_watts: float        # per-client power budget P_max
    sigma_n: float            # channel noise std (concrete float)
    delta_t: float            # aggregation period (seconds)
    transmit_delta: bool      # True: clients transmit dw_k; False: w_k
    pending_dtype: str = "float32"   # carry storage dtype for the (K, ...)
                              # planes: "float32" | "bfloat16" (opt-in
                              # half-footprint mode; f32 accumulation)
    group_period: int = 0     # grouped aggregation window N (Air-FedGA
                              # style): 0 = flat (cross-shard sync every
                              # period); N >= 1 = intra-pod partials every
                              # period, ONE cross-pod psum every N periods
    compress: str = ""        # compressed cohort payloads: "" = off (the
                              # uncompressed program, bit for bit); "topk" /
                              # "randmask" = slots carry an (m, s) plane on
                              # per-slot supports. Requires a cohort carry,
                              # transmit_delta, raveled params.
    compress_s: int = 0       # static compressed width s; s == d routes
                              # the dense stats/AirComp stages statically
                              # (identity compression, bit-identical)
    slot_dtype: str = ""      # compressed slot-value storage: "" resolves
                              # to pending_dtype; "float32" | "bfloat16" |
                              # "int8" (per-row absmax + stochastic
                              # rounding, f32 accumulation downstream)
    error_feedback: bool = False  # carry per-slot EF residuals + the (K, s)
                              # parked plane; compensation a = delta +
                              # parked residual is what gets compressed
    screen: bool = False      # per-row payload screening (containment):
                              # a row whose stats sweep shows a non-finite
                              # value — or a norm beyond screen_max_norm —
                              # is masked out of the superposition exactly
                              # like a phantom client (b = 0, zeroed
                              # payload row, sanitized per-row scalars).
                              # False emits the unscreened program op for
                              # op (trace-time branch).
    screen_max_norm: float = 0.0  # Byzantine norm fence: rows with
                              # ||payload|| > screen_max_norm are screened
                              # too (0 = finite-only screening)
    divergence_factor: float = 0.0  # post-update divergence detector:
                              # roll back to the last-good global when
                              # ||w_g_new|| > factor * max(||good||,
                              # DIVERGENCE_NORM_FLOOR). 0 = off (no
                              # good-global carry slot, program unchanged)


class GroupTopology(NamedTuple):
    """Static mesh-axis split for grouped aggregation (trace-time only)."""
    pod_axes: tuple           # client axes indexing the pod groups — the
                              # cross-pod sync psums over these every
                              # group_period periods
    intra_axes: tuple         # client axes inside a pod — the per-period
                              # partial superposition psums over these
                              # (may be empty: every shard its own pod)
    intra_shards: int         # prod of intra_axes extents — the held
                              # partial's replication count, so the sync can
                              # fold held/intra_shards into the all-axes psum


class RoundStreams(NamedTuple):
    """Per-driver callbacks: how this driver's shard of clients trains and
    draws its randomness. All callbacks are traced (called inside jit /
    shard_map); under sharding each returns this shard's rows of the SAME
    global draws the single-device form makes, so trajectories agree.
    """
    local_train: Callable     # (global tree, x, y, round) -> stacked tree
                              # of (K_local, ...) leaves ((K_local, d) for
                              # the raveled single-leaf federation)
    latencies: Callable       # (round) -> (K_local,) latency draws
    channel: Callable         # (round) -> (K_local,) |h_k| draws
    noise_key: Callable       # (round) -> AWGN key (replicated)
    scenario: Callable = None # (round) -> ((K_local,) available,
                              # (K_local,) dropped) bool masks, or None —
                              # None skips the mask stage at TRACE time, so
                              # the no-scenario program stays bit-identical
    cohort_train: Callable = None  # cohort mode: (global tree, x, y, round,
                              # (m,) slot client ids) -> (m, ...) stacked
                              # trained tree — the m-row twin of local_train
    sched_priority: Callable = None  # cohort mode: (round) -> (K_local,)
                              # f32 scheduling scores; highest-score idle
                              # available clients fill freed slots. Rows
                              # pinned to -inf are never schedulable (the
                              # sharded drivers' phantom fill).
    compress_mask: Callable = None   # compress='randmask': (round) ->
                              # (s,) i32 shared support — drawn from the
                              # counter stream (TAG_COMPRESS), REPLICATED
                              # across shards so every shard re-derives
                              # the identical per-round mask
    quant_key: Callable = None       # slot_dtype='int8': (round) -> PRNG
                              # key for the stochastic-rounding dither
                              # (TAG_QUANT; sharded drivers fold in the
                              # shard offset — per-row draws must differ
                              # across shards, unlike the mask)


# ---------------------------------------------------------------------------
# shared stage helpers (host server + fused/sharded core)
# ---------------------------------------------------------------------------

def round_factors(deltas, payload, global_vec, prev_global, stal, omega,
                  eps=1e-12, tp=None):
    """Stage 2 of the round, one delta-plane sweep: eq.-25 staleness
    factors rho_k, gradient-similarity factors theta_k, and the payload
    sq-norms the power constraint (7) needs — all from ONE fused pass
    over the stacked deltas (+ payload) via ``repro.kernels.ops
    .round_stats``. ``payload=None`` means the payload IS the deltas
    (transmit='delta'), so their sq-norms are reused instead of re-swept.

    Per-client along the leading axis and shard-local under the client
    mesh axis (every reduction runs over the model dims, which each shard
    holds whole — per-leaf partials accumulate locally, no collective —
    UNLESS an intra-client ``tp`` topology is passed: each shard then
    holds only its TP-local model block and the sweep closes with one
    small psum over ``tp.axes``; see ``kernels.round_stats
    .round_stats_tp``).

    Returns (rho, theta, w_norm2)."""
    from repro.kernels.ops import round_stats
    gdir = jax.tree_util.tree_map(jnp.subtract, global_vec, prev_global)
    dots, dn2, pn2, gn2 = round_stats(deltas, gdir, payload, tp=tp)
    gnorm = jnp.sqrt(gn2)
    den = jnp.sqrt(jnp.maximum(dn2, eps) * jnp.maximum(gn2, eps))
    cos = jnp.where(gnorm < 1e-12, 0.0, dots / den)
    theta = similarity_factor(cos)
    rho = staleness_factor(stal, omega)
    return rho, theta, (dn2 if payload is None else pn2)


def eq25_factors(pending, starts, global_vec, prev_global, stal, omega,
                 use_kernel: bool = False):
    """Host-reference form of stage 2 (the ``PAOTAServer`` state is
    (pending, starts), not carried deltas): derive the deltas, then run
    the same fused one-sweep stats the on-device core uses. ``use_kernel``
    is accepted for interface compatibility; kernel-vs-jnp routing is
    resolved by the lowering platform inside
    ``repro.kernels.ops.round_stats``.

    Returns (deltas pytree, rho, theta)."""
    del use_kernel
    deltas = jax.tree_util.tree_map(jnp.subtract, pending, starts)
    rho, theta, _ = round_factors(deltas, None, global_vec, prev_global,
                                  stal, omega)
    return deltas, rho, theta


def constraint7_powers(powers, payload, h, p_max, w_norm2=None):
    """Stage 4 — instantaneous power constraint (7) under the sampled
    channel: p_k <- min(p_k, |h_k| sqrt(P_max / ||w_k||^2)). The fused
    core passes ``w_norm2`` straight from the stage-2 stats sweep; the
    host reference leaves it None and tree-reduces the payload here
    (same chunked accumulation — ``client_sq_norms`` — so the two paths
    agree to the float op). Per-client, shard-local."""
    if w_norm2 is None:
        w_norm2 = client_sq_norms(payload)
    return jnp.minimum(powers, effective_power_cap(w_norm2, h, p_max))


def compressed_round_factors(values, idx, resid, resid_idx, global_vec,
                             prev_global, stal, omega, scale=None,
                             eps=1e-12):
    """Stage-2 twin of ``round_factors`` for the compressed cohort plane:
    the stats sweep runs over the (m, s) transmitted values + the EF
    residuals on their supports (``repro.kernels.ops.round_stats_
    compressed``) — never a dense (m, d) row. theta sees each slot's full
    reconstruction <v + e, gdir> (exact at s = d, the sparsity
    approximation below it); the returned payload norm is ||v||^2, the
    TRANSMITTED energy, which is what the power constraint (7) actually
    caps on the air. Raveled single-leaf only.

    Returns (rho, theta, w_norm2)."""
    from repro.kernels.ops import round_stats_compressed
    gdir = global_vec - prev_global
    dots, dn2, pn2, gn2 = round_stats_compressed(values, idx, resid,
                                                 resid_idx, gdir,
                                                 scale=scale)
    gnorm = jnp.sqrt(gn2)
    den = jnp.sqrt(jnp.maximum(dn2, eps) * jnp.maximum(gn2, eps))
    cos = jnp.where(gnorm < 1e-12, 0.0, dots / den)
    theta = similarity_factor(cos)
    rho = staleness_factor(stal, omega)
    return rho, theta, pn2


# divergence detector: a global whose norm sits below this floor compares
# against the floor instead (a near-zero-init model must be allowed to
# grow — factor * ~0 would flag every first update as divergent)
DIVERGENCE_NORM_FLOOR = 1.0


def _tree_sq_norm(tree):
    """||tree||^2 as one f32 scalar (sum over leaves; model-dims only, so
    it is shard-local under client sharding — the globals are replicated)."""
    total = jnp.float32(0.0)
    for leaf in jax.tree_util.tree_leaves(tree):
        total = total + jnp.sum(jnp.square(leaf.astype(jnp.float32)))
    return total


def _screen_ok(theta, w_norm2, rcfg: RoundCfg):
    """Per-row containment verdict from the stats sweep the round already
    ran: a corrupt payload row (NaN/Inf anywhere in it) surfaces as a
    non-finite theta or sq-norm — the sweep's reductions ARE the detector,
    no extra model-plane pass — and ``screen_max_norm`` adds a Byzantine
    norm fence on top. Returns (ok mask, sanitized theta, sanitized
    w_norm2): the sanitized per-row scalars are what keep a screened row
    from poisoning the water-filling bounds (NaN * b survives b = 0)."""
    ok = jnp.isfinite(theta) & jnp.isfinite(w_norm2)
    if rcfg.screen_max_norm > 0.0:
        ok = ok & (w_norm2 <= jnp.float32(rcfg.screen_max_norm) ** 2)
    return ok, jnp.where(ok, theta, 0.0), jnp.where(ok, w_norm2, 0.0)


def _zero_rows(tree, ok):
    """Zero the failing rows of a stacked tree: a screened row superposes
    exact +0.0 into every contraction — bit-identical to a never-scheduled
    client's b = 0 contribution — instead of 0 * NaN = NaN."""
    def leaf(l):
        m = ok.reshape((ok.shape[0],) + (1,) * (l.ndim - 1))
        return jnp.where(m, l, jnp.zeros((), l.dtype))
    return jax.tree_util.tree_map(leaf, tree)


def _divergence_rollback(new_global, new_prev, carry: RoundCarry,
                         rcfg: RoundCfg):
    """Post-update divergence detector: if ||w_g^{new}|| jumped beyond
    ``divergence_factor`` times the last-good norm (or is non-finite —
    the comparison is written so NaN lands on the diverged side), restore
    BOTH w_g and prev_global from the carry's last-good slot (the
    similarity direction collapses to zero for one round — the existing
    gnorm guard maps that to cos = 0) and keep the slot; otherwise the
    accepted global becomes the new last-good. Scalar-select logic over
    replicated leaves: no collectives, ONE extra model copy in the carry.

    Returns (global, prev, good_global, good_norm2, rolled_back f32)."""
    n_new = _tree_sq_norm(new_global)
    f2 = jnp.float32(rcfg.divergence_factor) ** 2
    limit = f2 * jnp.maximum(carry.good_norm2,
                             jnp.float32(DIVERGENCE_NORM_FLOOR) ** 2)
    diverged = ~(n_new <= limit)

    def sel(gd, cand):
        return jnp.where(diverged, gd, cand)

    new_global = jax.tree_util.tree_map(sel, carry.good_global, new_global)
    new_prev = jax.tree_util.tree_map(sel, carry.good_global, new_prev)
    good_n2 = jnp.where(diverged, carry.good_norm2, n_new)
    # accepted -> good slot IS the accepted global; diverged -> unchanged
    return (new_global, new_prev, new_global, good_n2,
            diverged.astype(jnp.float32))


def _storage_dtype(rcfg: RoundCfg):
    return jnp.dtype(rcfg.pending_dtype)


def _cast_rows(tree, dtype):
    return jax.tree_util.tree_map(lambda l: l.astype(dtype), tree)


def _carry_write(trained, new_global, carry: RoundCarry, row_select, dtype,
                 tp=None):
    """The restarters' trained rows into the carry's uncompressed planes:
    ``(pending, deltas)``, each row taken from ``trained`` where
    ``row_select`` picks it and kept from the carry elsewhere. The delta
    rows are f32 ``trained - new_global`` before the storage cast."""
    if tp is not None:
        # TP-active carry writes: the payload planes hold only this
        # device's TP-local block of each leaf, so the (TP-replicated)
        # trained rows and new global are sliced down to the block first
        # — after this the write is the general delta form below
        from repro.sharding.tp import tp_slice
        tdef = jax.tree_util.tree_structure(carry.deltas)
        tr_l = jax.tree_util.tree_leaves(trained)
        g_l = jax.tree_util.tree_leaves(new_global)
        dl_l = jax.tree_util.tree_leaves(carry.deltas)
        p_l = (jax.tree_util.tree_leaves(carry.pending)
               if carry.pending is not None else [None] * len(tr_l))
        new_p, new_d = [], []
        for tr, g, dl, p, dim in zip(tr_l, g_l, dl_l, p_l, tp.leaf_dims):
            if dim >= 0:
                tr = tp_slice(tr, dim + 1, tp)
                g = tp_slice(g, dim, tp)
            if p is not None:
                new_p.append(row_select(tr.astype(p.dtype), p))
            new_d.append(row_select((tr - g[None]).astype(dl.dtype), dl))
        pending = (jax.tree_util.tree_unflatten(tdef, new_p)
                   if carry.pending is not None else None)
        return pending, jax.tree_util.tree_unflatten(tdef, new_d)
    pending = None if carry.pending is None else jax.tree_util.tree_map(
        lambda tr, p: row_select(tr.astype(p.dtype), p),
        trained, carry.pending)
    if dtype == jnp.float32 and pending is not None:
        # derive the delta rows from the NEW pending (identical values:
        # ready rows of `pending` ARE the trained rows) — this lets XLA
        # fuse the raveled concat straight into both carry writes
        # instead of materializing a separate (K, d) trained plane
        deltas = jax.tree_util.tree_map(
            lambda p, dl, g: row_select(p - g[None], dl),
            pending, carry.deltas, new_global)
    else:
        # bf16 storage (the delta MUST come from the f32 trained rows —
        # deriving it from the already-rounded pending would cancel two
        # large rounded models instead of rounding one small delta),
        # and the pending-less transmit='delta' carry
        deltas = jax.tree_util.tree_map(
            lambda tr, dl, g: row_select((tr - g[None]).astype(dl.dtype),
                                         dl),
            trained, carry.deltas, new_global)
    return pending, deltas


def _slot_dtype(rcfg: RoundCfg) -> str:
    """Resolved compressed slot-value storage dtype."""
    return rcfg.slot_dtype or rcfg.pending_dtype


class _SlotPlanes(NamedTuple):
    """``_compress_plane``'s outputs, named by the carry fields they fill;
    the planes a scheme does not keep stay None."""
    deltas: jnp.ndarray                 # (m, s) stored values
    slot_idx: jnp.ndarray               # (m, s) i32 supports
    slot_scale: jnp.ndarray = None      # (m,) f32, int8 storage only
    slot_resid: jnp.ndarray = None      # (m, s) f32 EF residuals
    slot_resid_idx: jnp.ndarray = None  # (m, s) i32 residual supports


def _compress_plane(comp, *, rcfg: RoundCfg, streams: RoundStreams, t):
    """Compress freshly trained (m, d) f32 rows (EF-compensated deltas)
    into the carry's slot planes.

    Support: s == d is statically the identity (both schemes — the carry
    holds the dense rows on an arange support, so the stats/AirComp
    stages route dense and stay bit-identical); top-k picks each row's s
    largest-|.| coordinates; randmask broadcasts the round's shared
    counter-RNG mask. Storage: f32 (exact), bf16 (round-trip), or int8
    (per-row absmax + unbiased stochastic rounding, scale kept f32). The
    EF residual is the exact f32 complement of the row against its stored
    reconstruction, re-sparsified to width s for the carry.

    Returns the ``_SlotPlanes``."""
    m, d = comp.shape
    s = rcfg.compress_s
    if s >= d:
        idx = jnp.broadcast_to(jnp.arange(d, dtype=jnp.int32)[None], (m, d))
        vals = comp
    elif rcfg.compress == "topk":
        idx = topk_support(comp, s)
        vals = gather_rows(comp, idx)
    else:                                                   # randmask
        mask = streams.compress_mask(t)
        idx = jnp.broadcast_to(mask[None], (m, s))
        vals = gather_rows(comp, idx)
    sd = _slot_dtype(rcfg)
    scale = None
    if sd == "int8":
        stored, scale = quantize_int8_stochastic(vals, streams.quant_key(t))
        v_hat = dequantize_int8(stored, scale)
    elif sd == "bfloat16":
        stored = vals.astype(jnp.bfloat16)
        v_hat = stored.astype(jnp.float32)
    else:
        stored = v_hat = vals
    if not rcfg.error_feedback:
        return _SlotPlanes(stored, idx, scale)
    e = ef_residual(comp, idx, v_hat)
    return _SlotPlanes(stored, idx, scale, *sparsify(e, s))


# ---------------------------------------------------------------------------
# the round transition
# ---------------------------------------------------------------------------

# Where a cohort's slot plane differs from the dense client plane: how a
# row sees the (K,) state, the compressed stats and superposition, slot
# turnover and the residual hand-off. Each is chosen at trace time from
# the carry's structure (``slot_client`` set) or ``rcfg.compress``, and
# the dense plane takes the branch that emits nothing.

def _row_view(carry: RoundCarry, upl, stal):
    """b and the staleness of each payload row. Dense rows are the
    clients; a cohort slot reads its occupant's (K,) state, and a dead
    slot reads b = 0, exactly the sharded drivers' phantom-row masking."""
    if carry.slot_client is None:
        return (upl.astype(jnp.float32),
                jnp.where(upl, stal, 0).astype(jnp.float32))
    occ, live = carry.slot_client, carry.slot_live
    return ((live & upl[occ]).astype(jnp.float32),
            jnp.where(live, stal[occ], 0).astype(jnp.float32))


def _identity_support(carry: RoundCarry, rcfg: RoundCfg) -> bool:
    """Compressed slots at the static s == d identity: the dense stats
    and superposition stages run on the (dequantized) rows."""
    return rcfg.compress_s >= carry.global_vec.shape[0]


def _compressed_factors(carry: RoundCarry, stal, rcfg: RoundCfg):
    """Stage 2 over compressed slots: the stats sweep runs on the (m, s)
    values + EF residuals, never a dense (m, d) row; at the identity
    support the dense sweep runs on the dequantized rows (f32/bf16
    identity rows pass through untouched, so compress off and s == d
    agree bit for bit). Returns (rho, theta, w_norm2, rows, packed): the
    dequantized rows the dense stages read, and the stored (values,
    scales) the compressed superposition reads."""
    v_id = (carry.deltas if carry.slot_scale is None
            else dequantize_int8(carry.deltas, carry.slot_scale))
    if _identity_support(carry, rcfg):
        rho, theta, w_norm2 = round_factors(
            v_id, None, carry.global_vec, carry.prev_global, stal,
            rcfg.omega)
    else:
        rho, theta, w_norm2 = compressed_round_factors(
            carry.deltas, carry.slot_idx, carry.slot_resid,
            carry.slot_resid_idx, carry.global_vec, carry.prev_global, stal,
            rcfg.omega, scale=carry.slot_scale)
    return rho, theta, w_norm2, v_id, (carry.deltas, carry.slot_scale)


def _zero_packed(packed, ok):
    """Screened rows out of the compressed slots' stored values and their
    dequantization scales, so an int8 slot with a NaN absmax scale
    contributes 0 * 0, never 0 * NaN. None (uncompressed) stays None."""
    if packed is None:
        return None
    vals, scale = packed
    vals = _zero_rows(vals, ok)
    return vals, (None if scale is None else jnp.where(ok, scale, 0.0))


def _superpose(payload, packed, powers, b, carry: RoundCarry, *,
               rcfg: RoundCfg, streams: RoundStreams, axis_name, tp):
    """Eqs. 6+8 on the flat path: one superposition + AWGN +
    normalization. Compressed slots (``packed`` set, s < d) decompress
    inside the gather-superpose kernel (eq. 8 in d-space), the stored int8
    plane feeding it directly with its scale folded into the weights."""
    key = streams.noise_key(carry.t)
    if packed is not None and not _identity_support(carry, rcfg):
        vals, scale = packed
        return paota_aggregate_compressed(
            vals, carry.slot_idx, powers, b, key, rcfg.sigma_n,
            carry.global_vec.shape[0], scale=scale, axis_name=axis_name)
    return paota_aggregate_stacked(payload, powers, b, key, rcfg.sigma_n,
                                   axis_name=axis_name, tp=tp)


def _refill_slots(carry: RoundCarry, restart, avail, streams: RoundStreams):
    """Slot turnover: departing occupants (uploaded, or upload dropped in
    transit) free their slots, and available idle clients fill them in
    counter-RNG priority order (``streams.sched_priority``, descending
    ``lax.top_k``: an O(K log K) sort-plane op, no priority queue). Dead
    slots contribute nothing anywhere (live = False).

    Returns (depart, take, new_occ, new_live, sched_k): the departing
    slots, the slots that take a newly scheduled client, the new slot
    map, and the (K,) mask of the scheduled clients."""
    k_local = carry.ready.shape[0]
    occ, live = carry.slot_client, carry.slot_live
    m = occ.shape[0]
    depart = live & restart[occ]
    stay = live & ~depart
    in_flight = jnp.zeros((k_local,), bool).at[occ].max(stay, mode="drop")
    prio = streams.sched_priority(carry.t)
    score = jnp.where(avail & ~in_flight, prio, -jnp.inf)
    top_score, top_ids = jax.lax.top_k(score, m)
    n_cand = jnp.sum((top_score > -jnp.inf).astype(jnp.int32))
    free = ~stay
    free_rank = jnp.cumsum(free.astype(jnp.int32)) - 1
    take = free & (free_rank < n_cand)
    new_occ = jnp.where(take, top_ids[jnp.clip(free_rank, 0, m - 1)],
                        occ).astype(jnp.int32)
    new_live = stay | take
    sched_k = jnp.zeros((k_local,), bool).at[new_occ].max(take, mode="drop")
    return depart, take, new_occ, new_live, sched_k


def _hand_off_residuals(carry: RoundCarry, depart, take, new_occ,
                        rcfg: RoundCfg):
    """EF residuals on slot turnover: FIRST every departing slot parks its
    residual on the owning client's (K, s) row, THEN the newly scheduled
    occupants pick their parked rows back up (a same-round depart ->
    reschedule resumes the residual it just parked), THEN the consumed
    rows zero — the parked plane only ever holds errors nobody is
    currently training against.

    Returns (resid_val, resid_idx, resumed values, resumed supports)."""
    k_local = carry.ready.shape[0]
    park_row = jnp.where(depart, carry.slot_client, k_local)  # OOB: no write
    resid_val = carry.resid_val.at[park_row].set(carry.slot_resid,
                                                 mode="drop")
    resid_idx = carry.resid_idx.at[park_row].set(carry.slot_resid_idx,
                                                 mode="drop")
    pr_val = jnp.where(take[:, None], resid_val[new_occ], 0.0)
    if rcfg.screen:
        # a screened slot's parked residual may be the corrupt row's NaN
        # complement — resuming it would re-poison every later round of
        # an otherwise-recovered client
        pr_val = jnp.where(jnp.isfinite(pr_val), pr_val, 0.0)
    pr_idx = resid_idx[new_occ]
    consumed = jnp.where(take, new_occ, k_local)
    resid_val = resid_val.at[consumed].set(0.0, mode="drop")
    return resid_val, resid_idx, pr_val, pr_idx


def paota_round_step(carry: RoundCarry, x, y, *, rcfg: RoundCfg,
                     streams: RoundStreams, axis_name=None,
                     grouping: GroupTopology | None = None,
                     window_j: int = 0, tp=None):
    """One PAOTA aggregation period as a pure function.

    ``axis_name=None`` is the single-device form. With a mesh axis name
    (or tuple of names), the (K,) / (K, d) carry rows are this shard's
    clients and the cross-client reductions go through collectives.

    Active-cohort carry (``carry.slot_client`` set): the payload planes
    are (m, ...) slot rows instead of (K, ...). The scheduler/simulator
    state stays a dense (K,) plane; the slots gather their occupants'
    b, staleness and channel from it, run the same stats / water-filling
    / AirComp stages over m rows, and scatter the scheduler effects back.
    Idle clients sit at ``busy_lat = +inf`` (``slot_ready`` never flips
    them), and freed slots refill from the available idle pool
    (``_refill_slots``). Only the scheduled slots train
    (``streams.cohort_train``). Compressed slots (``rcfg.compress``) keep
    an (m, s) plane on per-slot supports with error-feedback residuals
    handed off through a (K, s) parked plane. At m = K (every client
    permanently slotted) the step is the dense round up to slot
    permutation — same uploader sets, same per-client draws, float
    reduction order the only difference; an all-masked cohort hits the
    same zero-uploader guard, holding w_g bit-identically.

    Intra-client TP (``tp``: ``repro.sharding.tp.TPTopology``, sharded
    pytree mode only): the payload planes additionally hold only this
    device's TP-local block of each leaf. Training stays replicated
    compute over the TP axes (full leaves from the replicated global);
    the stats sweep TP-slices the global direction and psums once over
    ``tp.axes``; the superposition's single model-sized psum spans
    clients x TP (superpose + gather in one collective) with the AWGN
    drawn at FULL shapes from the replicated key; and the carry writes
    slice the trained rows down to the TP-local block. ``tp=None`` (any
    TP extent-1 mesh) is op-for-op the historical program.

    Grouped aggregation (``rcfg.group_period`` N >= 1 with a
    ``grouping`` topology): ``window_j`` is this period's static position
    in the window. Non-sync periods (j < N-1) reduce the superposition
    over the intra-pod axes only and accumulate it into ``carry.held``
    weighted by the eq.-25 staleness factor of its age at the sync,
    rho(N-1-j) = Omega / (N-1-j + Omega) — the global model holds. The
    sync period (j = N-1) folds the held window into ONE psum over ALL
    client axes (held is intra-pod-replicated, so held/intra_shards under
    the all-axes psum equals its cross-pod sum), adds the single AWGN
    realization, normalizes, and applies the guarded update. At N=1 every
    period is a sync with held == 0, and since x + 0 is exact the program
    is op-for-op the flat path — grouped N=1 equals flat by construction.

    TP and grouped aggregation run on dense planes only; the drivers
    refuse the other combinations before any step is traced.

    Returns (next_carry, per-round metrics dict of replicated scalars)."""
    cohort = carry.slot_client is not None
    k_local = carry.ready.shape[0]
    grouped = grouping is not None and rcfg.group_period >= 1
    sync = (not grouped) or (window_j == rcfg.group_period - 1)

    def ksum(v, axis=None):
        s = jnp.sum(v, axis=axis)
        return s if axis_name is None else jax.lax.psum(s, axis_name)

    # 1. scheduler advance: who finished inside this period, staleness.
    # The finished test is the exact relative slot predicate over the
    # carried latency draws (repro.core.scheduler.slot_ready) — one f32
    # rounding, bit-identical to the host reference's mask at any horizon;
    # `time` is report-only. On a cohort only the in-flight clients carry
    # a finite latency draw, so only they can finish.
    with jax.named_scope("paota.schedule"):
        time = (carry.t + 1).astype(jnp.float32) * jnp.float32(rcfg.delta_t)
        ready, stal = sched_advance(carry.ready, carry.busy_lat,
                                    carry.model_round, carry.t, rcfg.delta_t)
        if streams.scenario is None:
            # no scenario: uploaders = restarters = the ready set
            avail = jnp.ones((k_local,), bool) if cohort else None
            upl = restart = ready
        else:
            # scenario masks (trace-time branch: the callback is None
            # unless a scenario can actually mask): unavailable-but-ready
            # clients HOLD their finished update and stay ready for a later
            # slot (staleness keeps growing); dropped uploads are lost in
            # transit but the client still restarts from the fresh broadcast
            avail, drop = streams.scenario(carry.t)
            upl = ready & avail & ~drop
            restart = ready & avail
        b, stal = _row_view(carry, upl, stal)

    # 2. staleness + gradient-similarity factors (eq. 25) + the payload
    # norms for constraint (7): ONE sweep over the carried delta plane
    # (sweep 1 of 2)
    with jax.named_scope("paota.stats"):
        packed = None
        if rcfg.compress:
            rho, theta, w_norm2, payload, packed = _compressed_factors(
                carry, stal, rcfg)
        else:
            payload = carry.deltas if rcfg.transmit_delta else carry.pending
            rho, theta, w_norm2 = round_factors(
                carry.deltas, None if rcfg.transmit_delta else carry.pending,
                carry.global_vec, carry.prev_global, stal, rcfg.omega, tp=tp)

        # 2b. containment (trace-time branch — screen=False emits the
        # unscreened program op for op): rows the stats sweep exposed as
        # corrupt (non-finite) or norm-fenced are masked out of this
        # round's superposition exactly like phantom clients — b = 0, the
        # payload row zeroed so every contraction sees exact +0.0, and the
        # per-row scalars sanitized so the water-filling bounds never touch
        # a NaN. The masking is shard-local and happens BEFORE the
        # collective, so the sharded round still compiles to ONE
        # cross-client psum.
        n_screened = jnp.float32(0.0)
        if rcfg.screen:
            ok, theta, w_norm2 = _screen_ok(theta, w_norm2, rcfg)
            n_screened = ksum(b * (~ok).astype(jnp.float32))
            b = b * ok.astype(jnp.float32)
            packed = _zero_packed(packed, ok)
            payload = _zero_rows(payload, ok)

    # 3. P2 -> beta -> powers (exact water-filling, pure jnp; the grid and
    # golden-section reductions over K run as psums under sharding). At a
    # grouped non-sync period only the pod's own clients superpose, so the
    # P2 reductions stay intra-pod (per-pod water level) — no cross-pod
    # collective outside the sync.
    with jax.named_scope("paota.power"):
        wf_axes = axis_name if sync else (grouping.intra_axes or None)
        p_max = jnp.full((b.shape[0],), rcfg.p_max_watts, jnp.float32)
        p2_rows = (rho, theta, b)
        if cohort:
            # P2 is solved over the slots in client-id order. Its objective
            # is flat near the optimum (cells a float ulp apart), so the
            # K-sums' association order picks beta; in slot order that
            # order followed the refill history and the host's SIMD width,
            # not the client set.
            order = jnp.argsort(jnp.where(carry.slot_live, carry.slot_client,
                                          k_local))
            p2_rows = tuple(v[order] for v in p2_rows)
        beta, p2_obj = waterfill_beta_jnp(p2_rows[0], p2_rows[1], p_max,
                                          p2_rows[2], rcfg.c1, rcfg.c0,
                                          axis_name=wf_axes)
        if cohort:
            beta = jnp.zeros_like(beta).at[order].set(beta)
        powers = power_from_beta(beta, rho, theta, p_max)

        # 4. instantaneous power constraint (7) under the sampled channel —
        # the payload norms came with the stats sweep, no extra pass
        h = streams.channel(carry.t)
        if cohort:
            h = jnp.where(carry.slot_live, h[carry.slot_client], 0.0)
        powers = constraint7_powers(powers, payload, h, rcfg.p_max_watts,
                                    w_norm2=w_norm2)

    # 5+6. AirComp superposition + AWGN + normalization (eqs. 6+8, sweep 2
    # of 2) and the zero-uploader-guarded update
    with jax.named_scope("paota.superpose"):
        held = carry.held
        if not grouped:
            # flat path: the superposition is ONE psum over the client axes
            # (or the single-device einsum) with the noise joining once
            # after
            agg, varsigma = _superpose(payload, packed, powers, b, carry,
                                       rcfg=rcfg, streams=streams,
                                       axis_name=axis_name, tp=tp)
            new_global, new_prev = guarded_global_update(
                carry.global_vec, carry.prev_global, agg, varsigma,
                delta=rcfg.transmit_delta)
        elif sync:
            partial = paota_partial_stacked(payload, powers, b)
            # held is replicated over the intra-pod shards, so scaling by
            # 1/intra_shards makes the all-axes psum reproduce its
            # cross-pod sum; at N=1 held == 0 and `partial + 0` is
            # bit-exact — the sync psum IS the flat path's. This is the
            # window's ONE cross-pod model-sized collective.
            scale = jnp.float32(1.0 / grouping.intra_shards)
            agg, varsigma = paota_finalize_stacked(
                partial + held[0] * scale, payload,
                streams.noise_key(carry.t), rcfg.sigma_n,
                axis_name=axis_name)
            new_global, new_prev = guarded_global_update(
                carry.global_vec, carry.prev_global, agg, varsigma,
                delta=rcfg.transmit_delta)
            held = jnp.zeros_like(held)
        else:
            # non-sync period: intra-pod partial only, weighted by the
            # eq.-25 staleness factor of its age at the sync slot (a static
            # Python float — the window position is unrolled); the global
            # holds.
            partial = paota_partial_stacked(
                payload, powers, b, axis_name=grouping.intra_axes or None)
            age = float(rcfg.group_period - 1 - window_j)
            held = held + jnp.float32(staleness_factor(age, rcfg.omega)) \
                * partial[None, :]
            varsigma = jnp.float32(0.0)
            new_global, new_prev = carry.global_vec, carry.prev_global

        # 6b. divergence rollback (trace-time branch; grouped non-sync
        # periods hold the global, so only update periods are checked) —
        # happens BEFORE the broadcast so a rolled-back round retrains from
        # the restored model
        good, good_n2 = carry.good_global, carry.good_norm2
        rolled = jnp.float32(0.0)
        if rcfg.divergence_factor > 0.0 and sync:
            new_global, new_prev, good, good_n2, rolled = \
                _divergence_rollback(new_global, new_prev, carry, rcfg)

    # 7. broadcast w^{r+1}: every restarter — uploader, or dropped uploader
    # whose update was lost in transit — begins fresh local training (at a
    # grouped non-sync period the rebroadcast model is the held global).
    # On a cohort the restarters leave their slots and the newly scheduled
    # clients take the broadcast instead.
    fields = {}
    fresh = restart
    with jax.named_scope("paota.schedule"):
        if cohort:
            depart, fresh, new_occ, new_live, sched_k = _refill_slots(
                carry, restart, avail, streams)
            fields.update(slot_client=new_occ, slot_live=new_live)
        t_next = carry.t + 1
        lat = streams.latencies(t_next)
        busy = carry.busy_lat
        if cohort:
            # departed-but-unscheduled clients go idle (busy_lat = +inf —
            # never ready again until rescheduled)
            departed = jnp.zeros((k_local,), bool).at[carry.slot_client].max(
                depart, mode="drop")
            idle = departed & ~sched_k
            ready = jnp.where(idle, False, ready)
            busy = jnp.where(idle, jnp.asarray(jnp.inf, busy.dtype), busy)
            restart = sched_k
        n_ready, n_lat, n_model = sched_broadcast(
            ready, busy, carry.model_round, restart, lat, t_next)
        resumed = None
        if rcfg.compress and rcfg.error_feedback:
            resid_val, resid_idx, *resumed = _hand_off_residuals(
                carry, depart, fresh, new_occ, rcfg)
            fields.update(resid_val=resid_val, resid_idx=resid_idx)

    # 8. local training: the carry's rows that take the broadcast (every
    # dense restarter, or the newly scheduled slots) get their trained
    # rows; the others keep their in-flight payload, and dead slots keep
    # masked garbage
    with jax.named_scope("paota.train"):
        if cohort:
            trained = streams.cohort_train(new_global, x, y, t_next, new_occ)
        else:
            trained = streams.local_train(new_global, x, y, t_next)
    dtype = _storage_dtype(rcfg)

    def row_select(new, old):
        m = fresh.reshape((fresh.shape[0],) + (1,) * (new.ndim - 1))
        return jnp.where(m, new, old)

    if rcfg.compress:
        with jax.named_scope("paota.compress"):
            # compressed store: the f32 delta rows are EF-compensated with
            # the resumed parked residuals (decompressed transiently — the
            # carry never holds an (m, d) plane), then support-selected,
            # stored, and their exact f32 residual re-sparsified.
            # Raveled single-leaf: `trained` is a bare (m, d) array.
            comp = trained - new_global[None]
            if resumed is not None:
                comp = comp + scatter_rows(*resumed, comp.shape[1])
            planes = _compress_plane(comp, rcfg=rcfg, streams=streams,
                                     t=t_next)

    with jax.named_scope("paota.carry_write"):
        if rcfg.compress:
            # the planes a scheme does not keep stay None; rows that take
            # no broadcast keep every old slot plane
            fields.update({f: row_select(v, getattr(carry, f))
                           for f, v in planes._asdict().items()
                           if v is not None})
        else:
            fields["pending"], fields["deltas"] = _carry_write(
                trained, new_global, carry, row_select, dtype, tp)

    n_upl = ksum(b)
    denom = jnp.maximum(n_upl, 1.0)
    if sync:
        # a zero-uploader P2 is vacuous (every candidate t is 0 and the
        # solver's ratio degenerates to c0/clamp ~ 1e22); report inf like
        # the host reference's skipped-round branch does
        p2_metric = jnp.where(n_upl > 0, p2_obj, jnp.inf)
    else:
        # non-sync period: the water level is per-pod, so p2_obj differs
        # across pods (replicated intra-pod). Report the mean over pods
        # that had uploaders — scalar psums only, never model-sized.
        intra = grouping.intra_axes
        pod_upl = jnp.sum(b)
        if intra:
            pod_upl = jax.lax.psum(pod_upl, intra)
        pod_has = pod_upl > 0
        obj_sum = jax.lax.psum(jnp.where(pod_has, p2_obj, 0.0),
                               grouping.pod_axes)
        n_active = jax.lax.psum(pod_has.astype(jnp.float32),
                                grouping.pod_axes)
        p2_metric = jnp.where(n_upl > 0,
                              obj_sum / jnp.maximum(n_active, 1.0), jnp.inf)
    out = {
        "n_participants": n_upl,
        "time": time,
        "mean_staleness": ksum(stal * b) / denom,
        "beta_mean": ksum(beta * b) / denom,
        # at a grouped non-sync period varsigma is reported 0.0 (nothing
        # normalized this period — the window's varsigma lands at the sync)
        "varsigma": jnp.where(varsigma > VARSIGMA_MIN, varsigma, 0.0),
        "p2_objective": p2_metric,
        "n_screened": n_screened,
        "rolled_back": rolled,
    }
    carry = carry._replace(t=t_next, time=time, ready=n_ready,
                           busy_lat=n_lat, model_round=n_model,
                           global_vec=new_global, prev_global=new_prev,
                           held=held, good_global=good, good_norm2=good_n2,
                           **fields)
    return carry, out


def init_round_carry(vec, x, y, *, streams: RoundStreams,
                     pending_dtype: str = "float32",
                     keep_pending: bool = True,
                     rcfg: RoundCfg | None = None, m: int = 0,
                     n_real=None) -> RoundCarry:
    """Round-0 kick-off: broadcast w_g^0 and precompute the local training
    (mirrors ``PAOTAServer.__init__``). ``vec`` is the params pytree
    (raveled = single (d,) leaf); shapes follow the streams' view of the
    federation (all K single-device; K/n per shard). The f32 delta
    (``trained - w_g^0``) is formed before the optional storage cast.
    ``keep_pending=False`` (transmit='delta') carries the delta plane
    only.

    ``m`` >= 1 builds the active-cohort carry: the first
    ``min(m, n_real)`` clients (in id order) fill the m slots and receive
    the broadcast; everyone else idles at ``busy_lat = +inf`` until a
    slot frees. ``m`` is this shard's slot count under sharding, and
    ``n_real`` (static or traced; default all) caps the live slots below
    the phantom padding. At m = K with no phantoms this is the dense
    carry plus the identity slot map.

    ``rcfg`` seeds the last-good rollback slot from w_g^0 when the
    divergence detector is on, and on compressed slots runs the round-0
    deltas through the scan's ``_compress_plane`` stage, with zeroed
    (K, s) parked-residual planes under error feedback."""
    busy, slots = None, {}
    if m:
        lat = streams.latencies(0)
        k = lat.shape[0]
        occ = jnp.arange(m, dtype=jnp.int32)
        n_real = k if n_real is None else n_real
        live = occ < jnp.minimum(jnp.asarray(m, jnp.int32),
                                 jnp.asarray(n_real, jnp.int32))
        sched_k = jnp.zeros((k,), bool).at[occ].max(live, mode="drop")
        busy = jnp.where(sched_k, lat, jnp.asarray(jnp.inf, lat.dtype))
        trained = streams.cohort_train(vec, x, y, 0, occ)
        slots.update(slot_client=occ, slot_live=live)
    else:
        trained = streams.local_train(vec, x, y, 0)
        k = jax.tree_util.tree_leaves(trained)[0].shape[0]
    dtype = jnp.dtype(pending_dtype)
    diverg = bool(rcfg is not None and rcfg.divergence_factor > 0.0)
    planes = None
    if rcfg is not None and rcfg.compress:
        # compressed payloads ride transmit='delta' (driver-enforced);
        # raveled single-leaf, so `trained` is a bare (m, d) array
        planes = _compress_plane(trained - vec[None], rcfg=rcfg,
                                 streams=streams, t=0)
    carry = RoundCarry(
        t=jnp.int32(0),
        time=jnp.float32(0.0),
        ready=jnp.zeros((k,), bool),
        busy_lat=streams.latencies(0) if busy is None else busy,
        model_round=jnp.zeros((k,), jnp.int32),
        global_vec=vec,
        prev_global=vec,
        pending=(_cast_rows(trained, dtype)
                 if keep_pending and planes is None else None),
        deltas=(jax.tree_util.tree_map(
            lambda tr, g: (tr - g[None]).astype(dtype), trained, vec)
            if planes is None else None),
        good_global=vec if diverg else None,
        good_norm2=_tree_sq_norm(vec) if diverg else None,
        **slots)
    if planes is not None:
        carry = carry._replace(**planes._asdict())
        if rcfg.error_feedback:
            s = planes.deltas.shape[1]
            carry = carry._replace(resid_val=jnp.zeros((k, s), jnp.float32),
                                   resid_idx=jnp.zeros((k, s), jnp.int32))
    return carry


def scan_rounds(carry: RoundCarry, x, y, n_rounds: int, *, rcfg: RoundCfg,
                streams: RoundStreams, axis_name=None, tp=None):
    """``lax.scan`` of ``paota_round_step`` over ``n_rounds`` periods —
    zero host round-trips inside. The scan nests cleanly under
    ``jax.shard_map`` (the sharded driver wraps THIS function, so a whole
    multi-round advance is one collective program). Drivers jit this with
    the carry donated (``donate_argnums``): the K x d planes of scan r
    are reused in place by scan r+1 instead of being copied across the
    call boundary. ``tp``: intra-client TP topology, threaded per step."""
    def step(c, _):
        return paota_round_step(c, x, y, rcfg=rcfg, streams=streams,
                                axis_name=axis_name, tp=tp)
    return jax.lax.scan(step, carry, None, length=n_rounds)


def scan_windows(carry: RoundCarry, x, y, n_windows: int, *, rcfg: RoundCfg,
                 streams: RoundStreams, axis_name, grouping: GroupTopology):
    """Grouped-aggregation scan: ``n_windows`` windows of
    ``rcfg.group_period`` periods each. The window is Python-UNROLLED
    inside the scan step (``window_j`` is static — the staleness weight and
    the sync/non-sync collective structure are baked per position), so the
    compiled scan body contains exactly ONE cross-pod model-sized
    all-reduce per window — the invariant the grouped benchmark's HLO
    check pins. Per-period metrics come back stacked (n_windows, N);
    callers reshape to the flat (n_rounds,) timeline."""
    def window(c, _):
        outs = []
        for j in range(rcfg.group_period):
            c, out = paota_round_step(c, x, y, rcfg=rcfg, streams=streams,
                                      axis_name=axis_name, grouping=grouping,
                                      window_j=j)
            outs.append(out)
        stacked = {k: jnp.stack([o[k] for o in outs]) for k in outs[0]}
        return c, stacked
    return jax.lax.scan(window, carry, None, length=n_windows)
