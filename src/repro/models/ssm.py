"""Mamba2 (SSD — state-space duality) blocks [arXiv:2405.21060].

Implements the chunked SSD algorithm for training/prefill (quadratic within
a chunk on the MXU, linear across chunks via a state recurrence) and the O(1)
recurrent step for decode. This is the TPU adaptation of the paper's GPU
kernel: chunk-local work is dense einsums (MXU-friendly), the cross-chunk
recurrence is a ``lax.scan`` carrying the (H, P, N) state.

Used by: mamba2-370m [ssm], zamba2-7b [hybrid, arXiv:2411.15242].
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import _normal


def _dims(cfg: ModelConfig):
    d_in = cfg.d_inner
    h = cfg.ssm_nheads
    p = cfg.ssm_head_dim
    g = cfg.ssm_ngroups
    n = cfg.ssm_state
    d_xbc = d_in + 2 * g * n
    return d_in, h, p, g, n, d_xbc


def init_mamba2(key, cfg: ModelConfig, dtype):
    d_in, h, p, g, n, d_xbc = _dims(cfg)
    ks = jax.random.split(key, 5)
    d_proj = 2 * d_in + 2 * g * n + h  # z, x, B, C, dt
    return {
        "in_proj": _normal(ks[0], (cfg.d_model, d_proj), dtype),
        "conv_w": _normal(ks[1], (cfg.conv_kernel, d_xbc), dtype, scale=0.2),
        "a_log": jnp.zeros((h,), jnp.float32),        # A = -exp(a_log) in (-inf,0)
        "dt_bias": jnp.full((h,), -2.0, jnp.float32),  # softplus(-2) ~ 0.13
        "skip_d": jnp.ones((h,), jnp.float32),
        "norm_scale": jnp.ones((d_in,), dtype),
        "out_proj": _normal(ks[4], (d_in, cfg.d_model), dtype,
                            scale=0.02 / math.sqrt(2.0 * cfg.num_layers)),
    }


def _split_proj(params, u, cfg: ModelConfig):
    d_in, h, p, g, n, d_xbc = _dims(cfg)
    zxbcdt = u @ params["in_proj"]
    z = zxbcdt[..., :d_in]
    xbc = zxbcdt[..., d_in:d_in + d_xbc]
    dt = zxbcdt[..., d_in + d_xbc:]
    return z, xbc, dt


def _causal_conv(params, xbc, conv_state=None):
    """Depthwise causal conv width K via shifted adds. xbc: (B, T, C).
    conv_state: (B, K-1, C) tail of previous tokens (decode/prefill chain)."""
    w = params["conv_w"]                      # (K, C)
    k = w.shape[0]
    if conv_state is None:
        pad = jnp.zeros(xbc.shape[:1] + (k - 1,) + xbc.shape[2:], xbc.dtype)
    else:
        pad = conv_state.astype(xbc.dtype)
    full = jnp.concatenate([pad, xbc], axis=1)          # (B, T+K-1, C)
    t = xbc.shape[1]
    out = sum(full[:, i:i + t, :] * w[i][None, None, :] for i in range(k))
    new_state = full[:, -(k - 1):, :] if k > 1 else full[:, :0, :]
    return jax.nn.silu(out), new_state


def _gated_norm(params, y, z, cfg: ModelConfig):
    dt = y.dtype
    y32 = (y * jax.nn.silu(z)).astype(jnp.float32)
    ms = jnp.mean(jnp.square(y32), axis=-1, keepdims=True)
    return (y32 * jax.lax.rsqrt(ms + cfg.norm_eps)
            * params["norm_scale"].astype(jnp.float32)).astype(dt)


def ssd_chunked(x, dt, a, B, C, cfg: ModelConfig, init_state=None,
                use_kernel: bool = False):
    """Chunked SSD forward.

    x: (Bz, T, H, P)  dt: (Bz, T, H)  a: (H,) negative
    B, C: (Bz, T, G, N). Returns (y (Bz,T,H,P), final_state (Bz,H,P,N)).
    use_kernel: route the intra-chunk quadratic part through
    ``repro.kernels.ops.ssd_intra_chunk`` — the Pallas kernel where the
    computation is lowered for a TPU, its jnp oracle elsewhere.
    """
    bz, t, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    q = min(cfg.ssm_chunk, t)
    pad = (-t) % q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
    tt = t + pad
    nc = tt // q
    rep = h // g  # heads per B/C group

    # reshape to chunks
    xc = x.reshape(bz, nc, q, h, p)
    dtc = dt.reshape(bz, nc, q, h)                       # (Bz,NC,Q,H)
    Bc = B.reshape(bz, nc, q, g, n)
    Cc = C.reshape(bz, nc, q, g, n)
    Bh = jnp.repeat(Bc, rep, axis=3)                     # (Bz,NC,Q,H,N)
    Ch = jnp.repeat(Cc, rep, axis=3)

    da = dtc * a[None, None, None, :]                    # log-decay per step (<0)
    cum = jnp.cumsum(da, axis=2)                         # (Bz,NC,Q,H)
    xdt = xc * dtc[..., None]

    if use_kernel:
        from repro.kernels.ops import ssd_intra_chunk
        gsz = bz * nc * h
        cum_f = cum.transpose(0, 1, 3, 2).reshape(gsz, q)
        b_f = Bh.transpose(0, 1, 3, 2, 4).reshape(gsz, q, n)
        c_f = Ch.transpose(0, 1, 3, 2, 4).reshape(gsz, q, n)
        x_f = xdt.transpose(0, 1, 3, 2, 4).reshape(gsz, q, p)
        y_f, st_f, dec_f = ssd_intra_chunk(cum_f, b_f, c_f, x_f)
        y_intra = y_f.reshape(bz, nc, h, q, p).transpose(0, 1, 3, 2, 4)
        chunk_state = st_f.reshape(bz, nc, h, n, p).transpose(0, 1, 2, 4, 3)
        chunk_decay = dec_f.reshape(bz, nc, h)
    else:
        # intra-chunk (dual / attention-like form)
        li = cum[:, :, :, None, :]                       # (Bz,NC,Q,1,H) query i
        lj = cum[:, :, None, :, :]                       # (Bz,NC,1,Q,H) key j
        decay = jnp.exp(jnp.clip(li - lj, -60.0, 0.0))   # (Bz,NC,Q,Q,H)
        causal = jnp.tril(jnp.ones((q, q), bool))[None, None, :, :, None]
        scores = jnp.einsum("bcihn,bcjhn->bcijh", Ch, Bh) * decay
        scores = jnp.where(causal, scores, 0.0)
        y_intra = jnp.einsum("bcijh,bcjhp->bcihp", scores, xdt)

        # chunk summaries: state contributed by each chunk.
        # cum[-1]-cum[j] <= 0 (negative log decays), so clip to [-60, 0].
        tail = jnp.exp(jnp.clip(cum[:, :, -1:, :] - cum, -60.0, 0.0))
        chunk_state = jnp.einsum("bcjhn,bcjhp->bchpn",
                                 Bh * tail[..., None], xdt)
        chunk_decay = jnp.exp(jnp.clip(cum[:, :, -1, :], -60.0, 0.0))

    # cross-chunk recurrence
    if init_state is None:
        init_state = jnp.zeros((bz, h, p, n), jnp.float32)

    def step(s, inp):
        cs, cd = inp                                      # (Bz,H,P,N), (Bz,H)
        s_out = s                                         # state BEFORE this chunk
        s_new = s * cd[:, :, None, None] + cs
        return s_new, s_out

    states = jnp.swapaxes(chunk_state, 0, 1).astype(jnp.float32)  # (NC,Bz,H,P,N)
    decays = jnp.swapaxes(chunk_decay, 0, 1)
    final_state, prev_states = jax.lax.scan(step, init_state, (states, decays))
    prev_states = jnp.swapaxes(prev_states, 0, 1)         # (Bz,NC,H,P,N)

    # inter-chunk output: C_i · (decay_to_i * S_prev)
    into = jnp.exp(jnp.clip(cum, -60.0, 0.0))             # decay from chunk start
    y_inter = jnp.einsum("bcihn,bchpn->bcihp",
                         Ch * into[..., None], prev_states.astype(Ch.dtype))

    y = (y_intra + y_inter).reshape(bz, tt, h, p)[:, :t]
    return y.astype(x.dtype), final_state


def init_ssm_state(cfg: ModelConfig, batch: int, dtype):
    d_in, h, p, g, n, d_xbc = _dims(cfg)
    return {
        "ssm": jnp.zeros((batch, h, p, n), jnp.float32),
        "conv": jnp.zeros((batch, cfg.conv_kernel - 1, d_xbc), dtype),
    }


def apply_mamba2(params, u, cfg: ModelConfig, state=None):
    """Full-sequence forward (train / prefill). u: (B, T, d_model).
    Returns (out (B,T,d_model), new_state dict)."""
    d_in, h, p, g, n, d_xbc = _dims(cfg)
    bz, t, _ = u.shape
    z, xbc, dt = _split_proj(params, u, cfg)
    conv_in = None if state is None else state["conv"]
    xbc, conv_state = _causal_conv(params, xbc, conv_in)
    x = xbc[..., :d_in].reshape(bz, t, h, p)
    B = xbc[..., d_in:d_in + g * n].reshape(bz, t, g, n)
    C = xbc[..., d_in + g * n:].reshape(bz, t, g, n)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
    a = -jnp.exp(params["a_log"])
    init_s = None if state is None else state["ssm"]
    y, final_state = ssd_chunked(x, dt, a, B, C, cfg, init_s)
    y = y + x * params["skip_d"][None, None, :, None].astype(y.dtype)
    y = _gated_norm(params, y.reshape(bz, t, d_in), z, cfg)
    out = y @ params["out_proj"]
    return out, {"ssm": final_state, "conv": conv_state}


def apply_mamba2_decode(params, u, state, cfg: ModelConfig):
    """Single-token recurrent step. u: (B, 1, d_model). O(1) in context length —
    this is why SSM/hybrid archs run long_500k."""
    d_in, h, p, g, n, d_xbc = _dims(cfg)
    bz = u.shape[0]
    z, xbc, dt = _split_proj(params, u, cfg)
    xbc, conv_state = _causal_conv(params, xbc, state["conv"])
    x = xbc[:, 0, :d_in].reshape(bz, h, p)
    B = xbc[:, 0, d_in:d_in + g * n].reshape(bz, g, n)
    C = xbc[:, 0, d_in + g * n:].reshape(bz, g, n)
    dt1 = jax.nn.softplus(dt[:, 0].astype(jnp.float32) + params["dt_bias"])  # (B,H)
    a = -jnp.exp(params["a_log"])
    decay = jnp.exp(dt1 * a[None, :])                     # (B,H)
    rep = h // g
    Bh = jnp.repeat(B, rep, axis=1)                       # (B,H,N)
    Ch = jnp.repeat(C, rep, axis=1)
    xdt = (x * dt1[..., None]).astype(jnp.float32)
    s_new = (state["ssm"] * decay[:, :, None, None]
             + jnp.einsum("bhn,bhp->bhpn", Bh.astype(jnp.float32), xdt))
    y = jnp.einsum("bhn,bhpn->bhp", Ch.astype(jnp.float32), s_new)
    y = y.astype(u.dtype) + x * params["skip_d"][None, :, None].astype(u.dtype)
    y = _gated_norm(params, y.reshape(bz, 1, d_in), z, cfg)
    out = y @ params["out_proj"]
    return out, {"ssm": s_new, "conv": conv_state}
