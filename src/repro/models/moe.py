"""Mixture-of-Experts layer: top-k router with GShard-style grouped
capacity dispatch.

Tokens are partitioned into groups of ``cfg.moe_group_size``; each group
computes its own one-hot dispatch/combine tensors, bounding the dispatch
memory to O(G * g * E * C) with C = g*k*cf/E (instead of the quadratic
ungrouped form). When the group axis is sharded over the mesh's data axis
and the expert axis over the EP axis, XLA SPMD turns the dispatch/combine
einsums into all-to-alls — the collective the roofline tracks.

Covers: llama4-maverick (128e top-1) [hf:meta-llama/Llama-4-Scout-17B-16E],
mixtral-8x22b (8e top-2, SWA) [arXiv:2401.04088].
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import _normal, apply_dense, constrain, shard_hint


def init_moe(key, cfg: ModelConfig, dtype):
    d, ff, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    ks = jax.random.split(key, 4)
    depth_scale = 0.02 / math.sqrt(2.0 * cfg.num_layers)
    return {
        "router": {"w": _normal(ks[0], (d, e), dtype)},
        # stacked expert weights, leading expert axis (sharded as EP)
        "gate": _normal(ks[1], (e, d, ff), dtype),
        "up": _normal(ks[2], (e, d, ff), dtype),
        "down": (float(depth_scale) / 0.02 * _normal(ks[3], (e, ff, d), dtype)
                 ).astype(dtype),
    }


def _group_capacity(g: int, cfg: ModelConfig) -> int:
    cap = int(cfg.capacity_factor * cfg.experts_per_token * g
              / max(cfg.num_experts, 1))
    return max(cap, 1)


def router_topk(logits, cfg: ModelConfig):
    """Top-k routing with load-balance aux loss (Switch/GShard style).

    logits: (..., E). Returns (weights (..., E), aux_loss scalar): weights
    nonzero only at chosen experts, rows sum to 1.
    """
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    k = cfg.experts_per_token
    topv, topi = jax.lax.top_k(probs, k)
    sel = jax.nn.one_hot(topi, cfg.num_experts, dtype=probs.dtype)
    weights = jnp.einsum("...k,...ke->...e",
                         topv / jnp.sum(topv, -1, keepdims=True), sel)
    # load-balance loss: E * sum_e f_e * p_e  (Switch Transformer eq. 4)
    flat_sel = sel.reshape(-1, sel.shape[-2], sel.shape[-1])
    f = jnp.mean(jnp.sum(flat_sel, axis=1), axis=0)
    p = jnp.mean(probs.reshape(-1, probs.shape[-1]), axis=0)
    aux = cfg.num_experts * jnp.sum(f * p)
    return weights, aux


def apply_moe(params, x, cfg: ModelConfig):
    """x: (B, T, d). Returns (out (B,T,d), aux_loss)."""
    b, t, d = x.shape
    n_tok = b * t
    g = min(cfg.moe_group_size, n_tok)
    use_smap = cfg.act_ep is not None and cfg.act_ep_size > 1
    if use_smap:
        # group count must be a multiple of the EP axis for the shard_map
        # dispatch (single-token decode pads up to ep groups of 1)
        ep = cfg.act_ep_size
        ng0 = max(1, (n_tok + g - 1) // g)
        ng0 = max(ep, ((ng0 + ep - 1) // ep) * ep)
        g = max(1, (n_tok + ng0 - 1) // ng0)
        pad = ng0 * g - n_tok
    else:
        # pad token count to a multiple of the group size
        pad = (-n_tok) % g
    xt = x.reshape(n_tok, d)
    if pad:
        xt = jnp.pad(xt, ((0, pad), (0, 0)))
    ng = (n_tok + pad) // g
    xg = xt.reshape(ng, g, d)                                 # (G, g, d)
    cap = _group_capacity(g, cfg)

    logits = apply_dense(params["router"], xg)                # (G, g, E)
    weights, aux = router_topk(logits, cfg)                   # (G, g, E)

    # per-group position of each token within its expert queue
    chosen = (weights > 0).astype(jnp.int32)                  # (G, g, E)
    pos_in_expert = jnp.cumsum(chosen, axis=1) * chosen - 1
    keep = chosen * (pos_in_expert < cap)
    weights = weights * keep

    slot = jax.nn.one_hot(jnp.clip(pos_in_expert, 0, cap - 1), cap,
                          dtype=x.dtype)                      # (G, g, E, C)
    disp = keep[..., None].astype(x.dtype) * slot
    combine = weights[..., None].astype(x.dtype) * slot

    # --- all-to-all boundary when E is mesh-sharded: constrain the
    # dispatched tensor to expert-sharded layout so GSPMD emits an
    # all-to-all (G-sharded -> E-sharded) instead of all-gathering the
    # full activation (EXPERIMENTS.md §Perf iter 2) ---
    exp_in = jnp.einsum("Gtd,Gtec->Gecd", xg, disp)           # (G, E, C, d)
    if use_smap:
        # explicit all-to-all dispatch: GSPMD's auto resharding chose
        # all-gathers of the full dispatched tensor (13.4 GB/layer for
        # llama4) — the shard_map region pins the Mesh-TF dataflow:
        # (G/ep, E, C, d) -all_to_all-> (G, E/ep, C, d) -> expert matmuls
        # (local) -> all_to_all back. §Perf iter 2d.
        exp_out = _expert_compute_shardmap(exp_in, params, cfg)
    else:
        exp_in = _constrain_ep4(exp_in, cfg)
        h = jnp.einsum("Gecd,edf->Gecf", exp_in, params["gate"])
        u = jnp.einsum("Gecd,edf->Gecf", exp_in, params["up"])
        act = jax.nn.silu(h) * u
        exp_out = jnp.einsum("Gecf,efd->Gecd", act, params["down"])
        exp_out = _constrain_ep4(exp_out, cfg)
    # --- combine ---
    out = jnp.einsum("Gecd,Gtec->Gtd", exp_out, combine)      # (G, g, d)
    out = _constrain_g(out, cfg)
    out = out.reshape(ng * g, d)[:n_tok]
    return out.reshape(b, t, d), aux


def _constrain_ep4(x, cfg: ModelConfig):
    """(G,E,C,d) -> expert-sharded over act_ep (fallback constraint path
    for expert counts that do not divide the EP axis)."""
    if cfg.act_ep is None:
        return x
    from jax.sharding import PartitionSpec as P
    return shard_hint(x, P(None, cfg.act_ep, None, None))


def _expert_compute_shardmap(exp_in, params, cfg: ModelConfig):
    """Expert FFN with explicit all-to-all dispatch over the EP axis.

    exp_in: (G, E, C, d) with G sharded over cfg.act_ep; expert weights
    (E, d, ff) with E sharded over cfg.act_ep (ff stays auto/TP-sharded).
    """
    from jax.sharding import PartitionSpec as P
    ep = cfg.act_ep

    def inner(x, gate, up, down):
        # local x: (G/n, E, C, d) -> (G, E/n, C, d)
        x = jax.lax.all_to_all(x, ep, split_axis=1, concat_axis=0, tiled=True)
        h = jnp.einsum("Gecd,edf->Gecf", x, gate)
        u = jnp.einsum("Gecd,edf->Gecf", x, up)
        act = jax.nn.silu(h) * u
        y = jnp.einsum("Gecf,efd->Gecd", act, down)
        # back: (G, E/n, C, d) -> (G/n, E, C, d)
        return jax.lax.all_to_all(y, ep, split_axis=0, concat_axis=1,
                                  tiled=True)

    smap = jax.shard_map(
        inner,
        in_specs=(P(ep, None, None, None), P(ep, None, None),
                  P(ep, None, None), P(ep, None, None)),
        out_specs=P(ep, None, None, None),
        axis_names={ep})
    return smap(exp_in, params["gate"], params["up"], params["down"])


def _constrain_g(x, cfg: ModelConfig):
    """(G,g,d) -> token-group-sharded over act_dp."""
    if not cfg.act_dp:
        return x
    from jax.sharding import PartitionSpec as P
    dp = tuple(cfg.act_dp)
    dp = dp[0] if len(dp) == 1 else dp
    return shard_hint(x, P(dp, None, None))
