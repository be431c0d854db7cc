"""Plain reference of the PAOTA aggregation period (arXiv:2305.04066), dense
client plane: every client holds its in-flight local model.

It shares no code with the program under test. It follows the same
semantics from the same inputs: the run's client data and initial weights
(made by the benchmark from the seed) and the same counter-based random
streams, which are part of the federation's definition: every draw is
``fold_in(fold_in(key, round), tag)`` of the scheduler key (latencies) or
of the server key (channel gains, channel noise, minibatches).

One period ``t``:

1. a client broadcast at round j with latency draw ``lat`` is ready at
   ``t`` when ``lat <= (t + 1 - j) * delta_t``; ready clients upload, with
   staleness ``t - j``;
2. eq. 25 factors: ``rho = Omega / (s + Omega)``, ``theta = (cos + 1) / 2``
   with ``cos`` between the client's local update and ``w^t - w^{t-1}``;
3. P2 over the uploaders by water-filling (the solver below is the
   program's own algorithm, copied: its grid and tie rule decide where on
   the flat part of P2 the powers land), powers by eq. 25;
4. constraint (7) under Rayleigh gains: ``p <= |h| sqrt(P_max / ||x||^2)``
   with ``x`` the transmitted payload;
5. eqs. 6 and 8: ``w = (sum_k b_k p_k x_k + n) / sum_k b_k p_k`` (model
   transmit), or ``w^t + that`` (delta transmit), held when nobody
   uploads;
6. uploaders restart from the new global with M local SGD steps.

Model-sized arithmetic runs in float32 at HIGHEST precision; the payload
is stored in the traffic's pending dtype, as the federation states. The
control runs the same code one precision below what the configuration
states (``lower=True``): bfloat16 arithmetic for the float32 model, and
the next narrower storage for the payload (bfloat16 for float32, float8
for bfloat16).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

TAG_LATENCY, TAG_CHANNEL, TAG_NOISE, TAG_BATCH = 0, 1, 2, 3

# Sec. IV-A constants
OMEGA, SMOOTH_L, EPS_BOUND, P_MAX = 3.0, 10.0, 0.05, 15.0
BANDWIDTH_HZ, N0_DBM_HZ = 20e6, -174.0
VARSIGMA_MIN = 1e-12
WATERFILL_GRID, WATERFILL_REFINE = 4096, 60
WATERFILL_TIE_RTOL = 32 * float(np.finfo(np.float32).eps)

LOWER = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn"}


class Matmul:
    """Matrix products in one dtype and precision."""

    def __init__(self, dtype, precision):
        self.dtype = jnp.dtype(dtype)
        self.precision = precision

    def __call__(self, a, b):
        return jnp.matmul(a.astype(self.dtype), b.astype(self.dtype),
                          precision=self.precision)

    def einsum(self, spec, a, b):
        return jnp.einsum(spec, a.astype(self.dtype), b.astype(self.dtype),
                          precision=self.precision)


@dataclass
class Seeds:
    sched: int      # scheduler key: latencies
    server: int     # server key: channel, noise, minibatches


def tag_key(key, round_idx, tag):
    return jax.random.fold_in(jax.random.fold_in(key, round_idx), tag)


def waterfill(rho, theta, p_max, b, c1, c0):
    """P2 by water-filling: t_k = clip(tau, lo_k, hi_k) on the active
    clients, tau on a grid then refined by golden section; returns beta."""
    p0 = jnp.clip(p_max * theta, 0.0, p_max)
    p1 = jnp.clip(p_max * rho, 0.0, p_max)
    lo, hi = jnp.minimum(p0, p1) * b, jnp.maximum(p0, p1) * b
    active = b > 0
    any_active = jnp.any(active)
    tau_lo = jnp.where(any_active, jnp.min(jnp.where(active, lo, jnp.inf)),
                       0.0)
    tau_hi = jnp.where(any_active, jnp.max(jnp.where(active, hi, -jnp.inf)),
                       1.0)

    def ratio(t):
        s = jnp.sum(t)
        return (c1 * jnp.sum(t * t) + c0) / jnp.maximum(s * s, 1e-30)

    taus = tau_lo + (tau_hi - tau_lo) * jnp.linspace(0.0, 1.0,
                                                     WATERFILL_GRID)
    ts = jnp.clip(taus[:, None], lo[None, :], hi[None, :]) * b[None, :]
    s = jnp.sum(ts, axis=1)
    vals = (c1 * jnp.sum(ts * ts, axis=1) + c0) / jnp.maximum(s * s, 1e-30)
    j = jnp.argmax(vals <= jnp.min(vals) * (1.0 + WATERFILL_TIE_RTOL))
    a = taus[jnp.maximum(j - 1, 0)]
    z = taus[jnp.minimum(j + 1, WATERFILL_GRID - 1)]
    gr = (np.sqrt(5.0) - 1.0) / 2.0
    for _ in range(WATERFILL_REFINE):
        m1, m2 = z - gr * (z - a), a + gr * (z - a)
        left = ratio(jnp.clip(m1, lo, hi) * b) < ratio(jnp.clip(m2, lo, hi)
                                                       * b)
        a, z = jnp.where(left, a, m1), jnp.where(left, m2, z)
    t = jnp.clip((a + z) / 2.0, lo, hi) * b
    dcoef = p_max * (rho - theta)
    interior = jnp.abs(dcoef) > 1e-12
    beta = jnp.where(interior, (t - p_max * theta)
                     / jnp.where(interior, dcoef, 1.0), 0.5)
    return jnp.clip(beta, 0.0, 1.0)


def _stack_set(stack, k, tree):
    return jax.tree_util.tree_map(lambda s, v: s.at[k].set(v.astype(s.dtype)),
                                  stack, tree)


class DenseReference:
    """The dense-plane federation of a cell, period by period."""

    def __init__(self, model, cfg, traffic, fed, w0, seeds: Seeds,
                 lower: bool = False):
        self.model, self.cfg, self.tr = model, cfg, traffic
        self.k = traffic["clients"]
        self.lower = lower
        compute = "bfloat16" if lower else "float32"
        self.mm = Matmul(compute, jax.lax.Precision.DEFAULT if lower
                         else jax.lax.Precision.HIGHEST)
        store = traffic["pending_dtype"]
        self.store = jnp.dtype(LOWER[store] if lower else store)
        self.model_dtype = jnp.dtype(compute)
        self.sched_key = jax.random.PRNGKey(seeds.sched)
        self.server_key = jax.random.PRNGKey(seeds.server)
        self.x = [jnp.asarray(fed.x[k]) for k in range(self.k)]
        self.y = [jnp.asarray(fed.y[k]) for k in range(self.k)]
        self.n = np.asarray(fed.n)
        self.w0 = jax.tree_util.tree_map(
            lambda a: jnp.asarray(a).astype(self.model_dtype), w0)
        self.d = sum(int(np.prod(np.shape(a)))
                     for a in jax.tree_util.tree_leaves(w0))
        sigma_n2 = BANDWIDTH_HZ * 10.0 ** ((N0_DBM_HZ - 30.0) / 10.0)
        self.sigma_n = float(jnp.sqrt(sigma_n2))
        self.c1 = SMOOTH_L * EPS_BOUND ** 2 * self.k
        self.c0 = 2.0 * SMOOTH_L * self.d * sigma_n2
        self._train = jax.jit(self._train_client)
        self._stats = jax.jit(self._stats_fn)
        self._powers = jax.jit(self._powers_fn)
        self._aggregate = jax.jit(self._aggregate_fn)

    # ---- local training -------------------------------------------------
    def _train_client(self, w, x, y, idx):
        lr = jnp.asarray(self.tr["lr"], self.model_dtype)

        def loss(p, xb, yb):
            return self.model.ref_loss(p, xb, yb, self.cfg, self.mm)

        for m in range(idx.shape[0]):
            g = jax.grad(loss)(w, x[idx[m]], y[idx[m]])
            w = jax.tree_util.tree_map(
                lambda p, gg: (p - lr * gg).astype(p.dtype), w, g)
        return w

    def plan(self, round_idx, k):
        key = jax.random.fold_in(
            tag_key(self.server_key, round_idx, TAG_BATCH), k)
        return jax.random.randint(key, (self.tr["local_steps"],
                                        self.tr["batch"]), 0, int(self.n[k]),
                                  dtype=jnp.int32)

    def latencies(self, round_idx):
        lo, hi = self.tr["latency_s"]
        return np.array(jax.random.uniform(
            tag_key(self.sched_key, round_idx, TAG_LATENCY), (self.k,),
            minval=lo, maxval=hi))

    def broadcast(self, w, round_idx, ids):
        for k in ids:
            tr = self._train(w, self.x[k], self.y[k], self.plan(round_idx, k))
            self.pending = _stack_set(self.pending, k, tr)
            self.deltas = _stack_set(
                self.deltas, k, jax.tree_util.tree_map(
                    lambda a, g: a.astype(jnp.float32)
                    - g.astype(jnp.float32), tr, w))

    # ---- round stages -----------------------------------------------------
    def _stats_fn(self, deltas, payload, gdir):
        f32 = jnp.float32
        dots = dn2 = pn2 = 0.0
        for dl, pl, g in zip(jax.tree_util.tree_leaves(deltas),
                             jax.tree_util.tree_leaves(payload),
                             jax.tree_util.tree_leaves(gdir)):
            d2 = dl.reshape(dl.shape[0], -1)
            p2 = pl.reshape(pl.shape[0], -1)
            dots = dots + self.mm(d2, g.reshape(-1)).astype(f32)
            dn2 = dn2 + self.mm.einsum("kd,kd->k", d2, d2).astype(f32)
            pn2 = pn2 + self.mm.einsum("kd,kd->k", p2, p2).astype(f32)
        gn2 = sum(jnp.sum(jnp.square(g.astype(f32)))
                  for g in jax.tree_util.tree_leaves(gdir))
        return dots, dn2, pn2, gn2

    def _powers_fn(self, dots, dn2, wn2, gn2, stal, b, h):
        den = jnp.sqrt(jnp.maximum(dn2, 1e-12) * jnp.maximum(gn2, 1e-12))
        cos = jnp.where(jnp.sqrt(gn2) < 1e-12, 0.0, dots / den)
        theta = (cos + 1.0) / 2.0
        rho = OMEGA / (stal + OMEGA)
        p_max = jnp.full((self.k,), P_MAX, jnp.float32)
        beta = waterfill(rho, theta, p_max, b, self.c1, self.c0)
        p = jnp.clip(p_max * (beta * rho + (1.0 - beta) * theta), 0.0, P_MAX)
        cap = h * jnp.sqrt(P_MAX / jnp.maximum(wn2, 1e-12))
        return jnp.minimum(p, cap), beta

    def _aggregate_fn(self, payload, bp, noise_key):
        sizes = [int(np.prod(a.shape[1:]))
                 for a in jax.tree_util.tree_leaves(payload)]
        noise = self.sigma_n * jax.random.normal(noise_key, (sum(sizes),),
                                                 jnp.float32)
        varsigma = jnp.maximum(jnp.sum(bp), VARSIGMA_MIN)
        leaves, treedef = jax.tree_util.tree_flatten(payload)
        out, off = [], 0
        for leaf, size in zip(leaves, sizes):
            acc = self.mm.einsum("k,kd->d", bp, leaf.reshape(leaf.shape[0],
                                                              -1))
            nz = noise[off:off + size]
            off += size
            out.append(((acc.astype(jnp.float32) + nz) / varsigma)
                       .reshape(leaf.shape[1:]))
        return jax.tree_util.tree_unflatten(treedef, out), jnp.sum(bp)

    # ---- the federation ---------------------------------------------------
    def run(self, periods: int):
        """Round-0 broadcast to every client, then ``periods`` periods.
        Returns the per-period rows and the final global (host float64)."""
        k, dt = self.k, np.float32(self.tr["delta_t"])
        delta_tx = self.tr["transmit"] == "delta"
        zeros = lambda dtype: jax.tree_util.tree_map(
            lambda a: jnp.zeros((k,) + a.shape, dtype), self.w0)
        self.pending, self.deltas = zeros(self.store), zeros(self.store)
        w = prev = self.w0
        ready = np.zeros(k, bool)
        busy = self.latencies(0)
        model_round = np.zeros(k, np.int32)
        self.broadcast(w, 0, range(k))
        rows = []
        for t in range(periods):
            ready |= busy <= (np.int32(t + 1) - model_round).astype(
                np.float32) * dt
            b = ready.astype(np.float32)
            stal = np.where(ready, t - model_round, 0).astype(np.float32)
            gdir = jax.tree_util.tree_map(
                lambda a, c: a.astype(jnp.float32) - c.astype(jnp.float32),
                w, prev)
            payload = self.deltas if delta_tx else self.pending
            dots, dn2, pn2, gn2 = self._stats(self.deltas, payload, gdir)
            u = jax.random.uniform(tag_key(self.server_key, t, TAG_CHANNEL),
                                   (k,), minval=1e-6, maxval=1.0)
            h = jnp.sqrt(-2.0 * jnp.log(u))
            powers, beta = self._powers(dots, dn2, dn2 if delta_tx else pn2,
                                        gn2, jnp.asarray(stal),
                                        jnp.asarray(b), h)
            bp = powers * jnp.asarray(b)
            agg, vs = self._aggregate(payload, bp, tag_key(
                self.server_key, t, TAG_NOISE))
            vs = float(vs)
            finite = all(bool(jnp.all(jnp.isfinite(a)))
                         for a in jax.tree_util.tree_leaves(agg))
            if vs > VARSIGMA_MIN and finite:
                new = (jax.tree_util.tree_map(
                    lambda g, a: g.astype(jnp.float32) + a, w, agg)
                    if delta_tx else agg)
                prev, w = w, jax.tree_util.tree_map(
                    lambda a: a.astype(self.model_dtype), new)
            rows.append({"n_participants": int(b.sum()),
                         "varsigma": vs if vs > VARSIGMA_MIN else 0.0,
                         "beta_mean": float(np.sum(np.asarray(beta) * b)
                                            / max(b.sum(), 1.0))})
            ids = np.flatnonzero(ready)
            lat = self.latencies(t + 1)
            busy[ids] = lat[ids]
            model_round[ids] = t + 1
            ready[ids] = False
            self.broadcast(w, t + 1, ids)
        final = jax.tree_util.tree_map(
            lambda a: np.asarray(a.astype(jnp.float32), np.float64), w)
        return rows, final


TAG_SCHED, TAG_COMPRESS, TAG_QUANT = 6, 8, 9
INT8_MAX, INT4_MAX = 127.0, 7.0


class CohortReference(DenseReference):
    """The active-cohort federation with compressed slots: at most m
    clients in flight, each slot holding its update on an s-coordinate
    support, error feedback, raveled model, delta transmit.

    Per period, beyond the dense stages: uploaders depart their slots;
    freed slots refill from the idle clients by the round's priority
    draw (highest first, in slot order); a departing slot parks its
    error-feedback residual on its client, and a client that is scheduled
    again resumes it. A new slot row compresses ``trained - w + parked
    residual``: the round's shared random support (``randmask``), then
    per-row absmax int8 with a stochastic-rounding dither drawn per slot
    row, and the residual of what the compression dropped, re-sparsified
    to its s largest entries. eq. 25 sees each slot's values plus its
    residual; constraint (7) caps by the transmitted values' energy. The
    control stores int4 levels (7 a side) where the cell states int8."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.m = self.tr["cohort_size"]
        self.s = min(self.d, max(1, int(round(self.d
                                               * self.tr["compress_ratio"]))))
        self._compress = jax.jit(self._compress_fn)
        self._cstats = jax.jit(self._cstats_fn)
        self._cagg = jax.jit(self._cagg_fn)
        self._cpowers = jax.jit(self._cpowers_fn)

    def _flat(self, tree):
        return jnp.concatenate([a.reshape(-1).astype(jnp.float32)
                                for a in jax.tree_util.tree_leaves(tree)])

    def _compress_fn(self, comp, round_idx):
        mask = jax.random.permutation(
            tag_key(self.server_key, round_idx, TAG_COMPRESS),
            self.d)[:self.s].astype(jnp.int32)
        idx = jnp.broadcast_to(mask[None], (self.m, self.s))
        vals = jnp.take_along_axis(comp, idx, axis=1)
        amax = jnp.max(jnp.abs(vals), axis=1)
        levels = INT4_MAX if self.lower else INT8_MAX
        scale = jnp.maximum(amax / levels, jnp.float32(1e-30))
        u = jax.random.uniform(tag_key(self.server_key, round_idx, TAG_QUANT),
                               vals.shape, jnp.float32)
        q = jnp.clip(jnp.floor(vals / scale[:, None] + u), -levels, levels)
        rows = jnp.arange(self.m)[:, None]
        e = comp.at[rows, idx].add(-q * scale[:, None])
        _, e_idx = jax.lax.top_k(jnp.abs(e), self.s)
        e_val = jnp.take_along_axis(e, e_idx, axis=1)
        return q, idx, scale, e_val, e_idx.astype(jnp.int32)

    def _cstats_fn(self, q, idx, scale, e_val, e_idx, gdir):
        f32 = jnp.float32
        v = q * scale[:, None]
        dots = (self.mm.einsum("ms,ms->m", v, gdir[idx])
                + self.mm.einsum("ms,ms->m", e_val, gdir[e_idx])).astype(f32)
        pn2 = self.mm.einsum("ms,ms->m", v, v).astype(f32)
        dn2 = pn2 + self.mm.einsum("ms,ms->m", e_val, e_val).astype(f32)
        return dots, dn2, pn2, jnp.sum(gdir * gdir)

    def _cpowers_fn(self, dots, dn2, pn2, gn2, stal, b, h, order):
        den = jnp.sqrt(jnp.maximum(dn2, 1e-12) * jnp.maximum(gn2, 1e-12))
        cos = jnp.where(jnp.sqrt(gn2) < 1e-12, 0.0, dots / den)
        theta = (cos + 1.0) / 2.0
        rho = OMEGA / (stal + OMEGA)
        p_max = jnp.full((self.m,), P_MAX, jnp.float32)
        beta = jnp.zeros((self.m,), jnp.float32).at[order].set(
            waterfill(rho[order], theta[order], p_max, b[order], self.c1,
                      self.c0))
        p = jnp.clip(p_max * (beta * rho + (1.0 - beta) * theta), 0.0, P_MAX)
        return jnp.minimum(p, h * jnp.sqrt(P_MAX / jnp.maximum(pn2, 1e-12)))

    def _cagg_fn(self, q, idx, scale, bp, noise_key):
        noise = self.sigma_n * jax.random.normal(noise_key, (self.d,),
                                                 jnp.float32)
        dense = jnp.zeros((self.m, self.d), jnp.float32).at[
            jnp.arange(self.m)[:, None], idx].add(q * scale[:, None])
        acc = self.mm.einsum("k,kd->d", bp, dense).astype(jnp.float32)
        return (acc + noise) / jnp.maximum(jnp.sum(bp), VARSIGMA_MIN), \
            jnp.sum(bp)

    def _train_slots(self, w, round_idx, ids, take, resid):
        """Compressed rows for the slots in ``take``: their new occupants'
        ``trained - w`` plus the residual each resumes."""
        comp = np.zeros((self.m, self.d), np.float32)
        for j in np.flatnonzero(take):
            k = int(ids[j])
            tr = self._train(w, self.x[k], self.y[k], self.plan(round_idx, k))
            comp[j] = np.asarray(self._flat(tr) - self._flat(w)) + resid[j]
        return self._compress(jnp.asarray(comp), round_idx)

    def run(self, periods: int):
        k, m, s = self.k, self.m, self.s
        dt = np.float32(self.tr["delta_t"])
        leaves, treedef = jax.tree_util.tree_flatten(self.w0)
        sizes = [int(np.prod(a.shape)) for a in leaves]
        w = prev = self._flat(self.w0)
        unflat = lambda v: jax.tree_util.tree_unflatten(treedef, [
            a.reshape(l.shape).astype(self.model_dtype) for a, l in zip(
                jnp.split(v, np.cumsum(sizes)[:-1]), leaves)])
        occ = np.arange(m)
        live = np.ones(m, bool)
        ready = np.zeros(k, bool)
        busy = np.full(k, np.inf, np.float32)
        busy[:m] = self.latencies(0)[:m]
        model_round = np.zeros(k, np.int32)
        parked = np.zeros((k, self.d), np.float32)
        slots = [np.asarray(a) for a in self._train_slots(
            unflat(w), 0, occ, live, np.zeros((m, self.d), np.float32))]
        rows = []
        for t in range(periods):
            q, idx, scale, e_val, e_idx = slots
            ready |= busy <= (np.int32(t + 1) - model_round).astype(
                np.float32) * dt
            b = (live & ready[occ]).astype(np.float32)
            stal = np.where(live, np.where(ready, t - model_round, 0)[occ],
                            0).astype(np.float32)
            dots, dn2, pn2, gn2 = self._cstats(q, idx, scale, e_val, e_idx,
                                               w - prev)
            u = jax.random.uniform(tag_key(self.server_key, t, TAG_CHANNEL),
                                   (k,), minval=1e-6, maxval=1.0)
            h = np.where(live, np.asarray(jnp.sqrt(-2.0 * jnp.log(u)))[occ],
                         0.0).astype(np.float32)
            order = np.argsort(np.where(live, occ, k), kind="stable")
            powers = self._cpowers(dots, dn2, pn2, gn2, stal, b, h, order)
            bp = powers * b
            agg, vs = self._cagg(q, idx, scale, bp,
                                 tag_key(self.server_key, t, TAG_NOISE))
            vs = float(vs)
            if vs > VARSIGMA_MIN and bool(jnp.all(jnp.isfinite(agg))):
                prev, w = w, w + agg
            rows.append({"n_participants": int(b.sum()),
                         "varsigma": vs if vs > VARSIGMA_MIN else 0.0})
            # slot turnover
            depart = live & ready[occ]
            stay = live & ~depart
            in_flight = np.zeros(k, bool)
            in_flight[occ[stay]] = True
            prio = np.asarray(jax.random.uniform(
                tag_key(self.sched_key, t, TAG_SCHED), (k,)))
            score = np.where(~in_flight, prio, -np.inf)
            top = np.argsort(-score, kind="stable")[:m]
            n_cand = int(np.sum(score[top] > -np.inf))
            free_rank = np.cumsum(~stay) - 1
            take = ~stay & (free_rank < n_cand)
            new_occ = np.where(take, top[np.clip(free_rank, 0, m - 1)], occ)
            departed = np.zeros(k, bool)
            departed[occ[depart]] = True
            sched = np.zeros(k, bool)
            sched[new_occ[take]] = True
            idle = departed & ~sched
            ready[idle] = False
            busy[idle] = np.inf
            lat = self.latencies(t + 1)
            ready[sched], busy[sched] = False, lat[sched]
            model_round[sched] = t + 1
            # error feedback hand-off: park, resume, consume
            for j in np.flatnonzero(depart):
                parked[occ[j]] = 0.0
                parked[occ[j], e_idx[j]] = e_val[j]
            resume = np.where(take[:, None], parked[new_occ], 0.0)
            parked[new_occ[take]] = 0.0
            fresh = [np.asarray(a) for a in self._train_slots(
                unflat(w), t + 1, new_occ, take, resume)]
            slots = [np.where(take.reshape((m,) + (1,) * (o.ndim - 1)), f, o)
                     for f, o in zip(fresh, slots)]
            occ, live = new_occ, stay | take
        final = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64),
                                       unflat(w))
        return rows, final
