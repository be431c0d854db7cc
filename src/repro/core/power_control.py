"""Power-control optimization — Section III-B.

The transmit power of client k trades off staleness vs gradient similarity
(eq. 25):

    p_k = p_max_k * ( beta_k * rho_k + (1 - beta_k) * theta_k )
    rho_k   = Omega / (s_k + Omega)                      (staleness factor)
    theta_k = (cos(dw_k, w_g^t - w_g^{t-1}) + 1) / 2     (similarity factor)

Minimizing the controllable part of the convergence bound G^r (Theorem 1,
terms (d)+(e)) over beta in [0,1]^K is the fractional program P2:

    min_beta  h1(beta)/h2(beta)
    h1 = L eps^2 K * sum_k b_k p_k^2 + 2 L d sigma_n^2      (term d + e numer.)
    h2 = (sum_k b_k p_k)^2                                  (normalizer^2)

with p = P_max (theta + D beta), D = diag(rho - theta) — both h1 and h2 are
convex quadratics in beta, exactly the paper's P2 structure (their G is the
diagonal L eps^2 K * diag(b) instance, their Q the rank-one b b^T instance).

Solvers (repro.core.dinkelbach): the paper-faithful Dinkelbach loop with a
piecewise-linear 0-1 MIP inner step (repro.core.milp — CPLEX replaced by a
pure-python branch & bound), plus two beyond-paper inner solvers validated
against it (projected gradient, and an exact KKT water-filling solver that
exploits the diagonal+rank-one structure; see DESIGN.md §3).
"""
from __future__ import annotations

import functools
import operator
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np


def staleness_factor(s, omega: float):
    """rho_k = Omega / (s_k + Omega); s_k = rounds the model is stale."""
    return omega / (s + omega)


def similarity_factor(cos_sim):
    """theta_k = (cos + 1)/2 in [0, 1]."""
    return (cos_sim + 1.0) / 2.0


# ---------------------------------------------------------------------------
# tree-reduced per-client scalars
#
# The federated model is a params pytree whose leaves are client-stacked
# (K, ...) arrays; a raveled federation is just the single-(K, D)-leaf
# instance (a bare jnp array IS a one-leaf pytree, so the raveled callers
# need no adapter and execute the exact historical op sequence). Every
# cross-leaf scalar is accumulated as per-leaf partials summed in
# tree_flatten order — under a mesh client axis these per-client values are
# shard-local (the reduction runs over the model dims, which every shard
# holds whole), so none of them costs a collective.
# ---------------------------------------------------------------------------

def _leaf2d(x):
    """(K, ...) leaf -> (K, prod(trailing)) view; identity for (K, D)."""
    return x.reshape((x.shape[0], -1))


def _accumulate(parts):
    return functools.reduce(operator.add, parts)


def client_sq_norms(tree, tp_axes=None):
    """(K,) per-client ||.||^2 over every leaf's trailing dims.

    Computed as a batched dot (``einsum kd,kd->k``), not ``sum(x*x, -1)``
    — XLA-CPU materializes the (K, d) square for the latter (an extra
    full write+read of the plane) but contracts the batched dot in one
    streaming pass. Same formulation as the fused round-stats sweep
    (``repro.kernels.round_stats``), so the host reference's constraint-
    (7) norms stay bit-identical to the fused core's.

    ``tp_axes``: mesh axis name(s) when every leaf's trailing dims are
    this shard's TP-local block under ``jax.shard_map`` — the accumulated
    partial is psum'd over them so every TP shard returns the full-model
    norm. Callers with mixed sharded/replicated leaves split the tree
    first (``repro.kernels.round_stats.round_stats_tp`` does)."""
    out = _accumulate([jnp.einsum("kd,kd->k", _leaf2d(l), _leaf2d(l),
                                  precision=jax.lax.Precision.HIGHEST)
                       for l in jax.tree_util.tree_leaves(tree)])
    return out if not tp_axes else jax.lax.psum(out, tp_axes)


def client_dots(tree, vec_tree, tp_axes=None):
    """(K,) per-client <leaf_k, vec> accumulated across leaves;
    ``tp_axes`` as in ``client_sq_norms`` (vec_tree leaves must be the
    matching TP-local blocks)."""
    out = _accumulate([jnp.matmul(_leaf2d(l), g.reshape(-1),
                                  precision=jax.lax.Precision.HIGHEST)
                       for l, g in zip(jax.tree_util.tree_leaves(tree),
                                       jax.tree_util.tree_leaves(vec_tree))])
    return out if not tp_axes else jax.lax.psum(out, tp_axes)


def global_sq_norm(vec_tree, tp_axes=None):
    """Scalar ||vec||^2 over all leaves of an unstacked params tree;
    ``tp_axes`` as in ``client_sq_norms``."""
    out = _accumulate([jnp.sum(g * g)
                       for g in jax.tree_util.tree_leaves(vec_tree)])
    return out if not tp_axes else jax.lax.psum(out, tp_axes)


def cosine_similarity(deltas, global_dir, use_kernel: bool = False, eps=1e-12):
    """cos(dw_k, g) per client: stacked deltas pytree ((K, ...) leaves — a
    bare (K, D) matrix is the single-leaf case) vs the matching global
    direction pytree ((...) leaves / a (D,) vector)."""
    if use_kernel:
        from repro.kernels.ops import cosine_sim
        return cosine_sim(deltas, global_dir)
    num = client_dots(deltas, global_dir)
    den = jnp.sqrt(jnp.maximum(client_sq_norms(deltas), eps)
                   * jnp.maximum(global_sq_norm(global_dir), eps))
    return num / den


def power_from_beta(beta, rho, theta, p_max):
    """Eq. (25). All (K,) vectors; result clipped to [0, p_max] (cond. 7)."""
    p = p_max * (beta * rho + (1.0 - beta) * theta)
    return jnp.clip(p, 0.0, p_max)


@dataclass(frozen=True)
class P2Problem:
    """Quadratic-ratio data for P2 (all numpy, solver-side)."""
    rho: np.ndarray      # (K,)
    theta: np.ndarray    # (K,)
    p_max: np.ndarray    # (K,)
    b: np.ndarray        # (K,) in {0,1}
    c1: float            # L * eps^2 * K      (term-d scale)
    c0: float            # 2 * L * d * sigma_n^2  (term-e numerator)

    @property
    def K(self) -> int:
        return len(self.rho)

    def power(self, beta: np.ndarray) -> np.ndarray:
        p = self.p_max * (beta * self.rho + (1 - beta) * self.theta)
        return np.clip(p, 0.0, self.p_max)

    def h1(self, beta: np.ndarray) -> float:
        p = self.power(beta) * self.b
        return float(self.c1 * np.sum(p * p) + self.c0)

    def h2(self, beta: np.ndarray) -> float:
        p = self.power(beta) * self.b
        s = np.sum(p)
        return float(s * s)

    def objective(self, beta: np.ndarray) -> float:
        """P2: h1/h2 (minimize). Equivalently maximize h2/h1 (P3 form)."""
        return self.h1(beta) / max(self.h2(beta), 1e-30)

    # ---- quadratic-form coefficients (paper's G, g, g0, Q, q, q0) ----
    def quadratics(self):
        """h1 = b'Gb + g'b + g0 ; h2 = b'Qb + q'b + q0 over beta (unclipped)."""
        pm, th, d = self.p_max, self.theta, (self.rho - self.theta)
        m = self.b.astype(float)
        # p_k = pm_k (th_k + d_k beta_k); active entries only
        A = pm * d * np.sqrt(m)            # sqrt-mask keeps G diagonal PSD
        Bc = pm * th * np.sqrt(m)
        G = self.c1 * np.diag(A * A)
        g = 2 * self.c1 * A * Bc
        g0 = self.c1 * float(Bc @ Bc) + self.c0
        u = pm * d * m
        v = pm * th * m
        Q = np.outer(u, u)
        q = 2 * float(np.sum(v)) * u
        q0 = float(np.sum(v)) ** 2
        return (G, g, g0), (Q, q, q0)


def p2_constants(smooth_l: float, eps_bound: float, k: int, model_dim: int,
                 sigma_n2: float):
    """Theorem-1 constants of P2: c1 = L eps^2 K (term-d scale) and
    c0 = 2 L d sigma_n^2 (term-e numerator). Shared by the numpy problem
    builder and the fused on-device solver."""
    return smooth_l * eps_bound ** 2 * k, 2.0 * smooth_l * model_dim * sigma_n2


def build_p2(rho, theta, p_max, b, *, smooth_l: float, eps_bound: float,
             model_dim: int, sigma_n2: float) -> P2Problem:
    """Assemble P2 from Theorem-1 constants: c1 = L eps^2 K, c0 = 2 L d sigma^2."""
    rho = np.asarray(rho, float)
    c1, c0 = p2_constants(smooth_l, eps_bound, len(rho), model_dim, sigma_n2)
    return P2Problem(
        rho=rho, theta=np.asarray(theta, float),
        p_max=np.asarray(p_max, float), b=np.asarray(b, float),
        c1=c1, c0=c0,
    )
