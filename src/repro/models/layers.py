"""Core transformer layers: GQA/SWA attention (train / prefill / decode),
RoPE, SwiGLU MLP, RMSNorm and OLMo-style non-parametric LayerNorm.

Pure-functional style: ``init_*`` returns a param pytree (nested dicts of
jnp arrays), ``apply_*`` consumes it. No framework dependency — this keeps
sharding annotation (PartitionSpec trees) fully explicit in repro.sharding.
"""
from __future__ import annotations

import functools
import math

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def _normal(key, shape, dtype, scale=0.02):
    return (scale * jax.random.normal(key, shape)).astype(dtype)


def init_dense(key, d_in: int, d_out: int, dtype, scale: float = 0.02):
    return {"w": _normal(key, (d_in, d_out), dtype, scale)}


def apply_dense(params, x):
    return x @ params["w"]


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def init_rmsnorm(d: int, dtype):
    return {"scale": jnp.ones((d,), dtype)}


def apply_norm(params, x, cfg: ModelConfig):
    """RMSNorm (llama family) or non-parametric LayerNorm (OLMo)."""
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    if cfg.norm == "nonparam_ln":
        # OLMo [arXiv:2402.00838]: LayerNorm without learnable affine params.
        mu = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.var(x32, axis=-1, keepdims=True)
        return ((x32 - mu) * jax.lax.rsqrt(var + cfg.norm_eps)).astype(dt)
    ms = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(ms + cfg.norm_eps)
    if params is not None:
        y = y * params["scale"].astype(jnp.float32)
    return y.astype(dt)


def maybe_init_norm(d: int, cfg: ModelConfig, dtype):
    return None if cfg.norm == "nonparam_ln" else init_rmsnorm(d, dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_rotate(x, positions, theta: float):
    """Apply rotary embedding. x: (..., T, H, D), positions: (..., T)."""
    d = x.shape[-1]
    half = d // 2
    freq = jnp.exp(-jnp.log(theta) * (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[..., :, None].astype(jnp.float32) * freq  # (..., T, half)
    cos = jnp.cos(ang)[..., :, None, :]  # (..., T, 1, half)
    sin = jnp.sin(ang)[..., :, None, :]
    x1, x2 = x[..., :half], x[..., half:2 * half]
    rot = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    if d > 2 * half:  # odd head_dim tail passes through
        rot = jnp.concatenate([rot, x[..., 2 * half:]], axis=-1)
    return rot.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention (GQA, optional sliding window) — train / prefill / decode
# ---------------------------------------------------------------------------

def shard_hint(x, spec):
    """``with_sharding_constraint`` against the mesh set by
    ``jax.set_mesh``. With no mesh set there is nothing to constrain to
    and ``x`` passes through; any other error propagates."""
    if jax.sharding.get_abstract_mesh().empty:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def constrain(x, cfg: ModelConfig, kind: str):
    """Activation sharding constraint (no-op unless launch.steps set the
    hints and a mesh is set). kind: 'btd' (batch,seq,d) | 'bthd'
    (batch,seq,heads,hd) | 'btf' (batch,seq,ffn). Leading batch dim ->
    cfg.act_dp axes; head/ffn dim -> cfg.act_tp. See EXPERIMENTS.md §Perf
    iter 1."""
    if not cfg.act_dp and cfg.act_tp is None:
        return x
    from jax.sharding import PartitionSpec as P
    dp = tuple(cfg.act_dp) or None
    if dp is not None and len(dp) == 1:
        dp = dp[0]
    tp = cfg.act_tp
    spec = {
        "btd": P(dp, None, None),
        # sequence parallelism (§Perf iter F): residual-stream activations
        # sharded over the TP axis on the sequence dim — row-parallel
        # projections emit reduce-scatters instead of all-reduces
        "btd_seq": P(dp, tp, None),
        "bthd": P(dp, None, tp, None),
        "btf": P(dp, None, tp),
    }[kind]
    return shard_hint(x, spec)


def init_attention(key, cfg: ModelConfig, dtype):
    d, h, hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    depth_scale = 0.02 / math.sqrt(2.0 * cfg.num_layers)
    return {
        "wq": init_dense(ks[0], d, h * hd, dtype),
        "wk": init_dense(ks[1], d, hkv * hd, dtype),
        "wv": init_dense(ks[2], d, hkv * hd, dtype),
        "wo": {"w": _normal(ks[3], (h * hd, d), dtype, depth_scale)},
    }


def _gqa_scores(q, k, cfg: ModelConfig):
    """q: (B,T,Hq,D)  k: (B,S,Hkv,D) -> logits (B,Hkv,G,T,S)."""
    b, t, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, t, hkv, g, d)
    logits = jnp.einsum("btkgd,bskd->bkgts", qg.astype(jnp.float32),
                        k.astype(jnp.float32))
    return logits / jnp.sqrt(d).astype(jnp.float32)


def _attend(q, k, v, mask, cfg: ModelConfig):
    """mask: broadcastable to (B,1,1,T,S) boolean — True = attend."""
    logits = _gqa_scores(q, k, cfg)
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    b, t = q.shape[0], q.shape[1]
    hkv, g, d = k.shape[2], q.shape[2] // k.shape[2], v.shape[3]
    out = jnp.einsum("bkgts,bskd->btkgd", probs, v.astype(jnp.float32))
    return out.reshape(b, t, hkv * g, d).astype(q.dtype)


def causal_window_mask(t_positions, s_positions, window: Optional[int]):
    """True where query at t may attend key at s (causal, optional window)."""
    tq = t_positions[..., :, None]
    sk = s_positions[..., None, :]
    m = sk <= tq
    if window is not None:
        m = m & (sk > tq - window)
    return m


ATTN_CHUNK_THRESHOLD = 2048   # switch to the scan/flash path beyond this S
ATTN_KV_CHUNK = 1024


def _chunk_valid(pj, q_pos, window, causal):
    """pj: (B,c) float key positions (-1 = pad); q_pos: (B,T) float."""
    valid = (pj[:, None, :] >= 0)
    if causal:
        valid = valid & (pj[:, None, :] <= q_pos[:, :, None])
    if window is not None:
        valid = valid & (pj[:, None, :] > q_pos[:, :, None] - window)
    return valid  # (B, T, c)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _flash(q, k, v, q_pos, k_pos, window, causal, chunk):
    """Flash attention with O(T*chunk) memory in BOTH passes.

    q: (B,T,Hq,D); k/v: (B,S,Hkv,D); q_pos/k_pos: float32 positions
    (-1 = padding). The backward recomputes per-chunk probabilities from
    the saved logsumexp — the full (T,S) matrix never exists; without this
    custom VJP the train_4k dry-run needed 684 GB/chip of residuals.
    """
    out, _ = _flash_fwd_impl(q, k, v, q_pos, k_pos, window, causal, chunk)
    return out


def _flash_fwd_impl(q, k, v, q_pos, k_pos, window, causal, chunk):
    bz, t, hq, d = q.shape
    s, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    pad = (-s) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)), constant_values=-1.0)
    nc = (s + pad) // chunk
    qg = (q.reshape(bz, t, hkv, g, d).astype(jnp.float32)
          * (1.0 / math.sqrt(d)))

    kc = jnp.moveaxis(k.reshape(bz, nc, chunk, hkv, d), 1, 0)
    vc = jnp.moveaxis(v.reshape(bz, nc, chunk, hkv, d), 1, 0)
    pc = jnp.moveaxis(k_pos.reshape(bz, nc, chunk), 1, 0)

    m0 = jnp.full((bz, hkv, g, t), -1e30, jnp.float32)
    l0 = jnp.zeros((bz, hkv, g, t), jnp.float32)
    a0 = jnp.zeros((bz, t, hkv, g, d), jnp.float32)

    def body(carry, xs):
        m, l, acc = carry
        kj, vj, pj = xs
        logits = jnp.einsum("btkgd,bskd->bkgts", qg, kj.astype(jnp.float32))
        valid = _chunk_valid(pj, q_pos, window, causal)
        logits = jnp.where(valid[:, None, None, :, :], logits, -1e30)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        p = jnp.exp(logits - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bkgts,bskd->btkgd", p, vj.astype(jnp.float32))
        acc_new = acc * corr.transpose(0, 3, 1, 2)[..., None] + pv
        return (m_new, l_new, acc_new), 0.0

    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kc, vc, pc))
    out = acc / jnp.maximum(l, 1e-30).transpose(0, 3, 1, 2)[..., None]
    lse = m + jnp.log(jnp.maximum(l, 1e-30))              # (B,Hkv,G,T)
    return out.reshape(bz, t, hq, d).astype(q.dtype), lse


def _flash_fwd(q, k, v, q_pos, k_pos, window, causal, chunk):
    out, lse = _flash_fwd_impl(q, k, v, q_pos, k_pos, window, causal, chunk)
    return out, (q, k, v, q_pos, k_pos, out, lse)


def _flash_bwd(window, causal, chunk, res, dout):
    q, k, v, q_pos, k_pos, out, lse = res
    bz, t, hq, d = q.shape
    s, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    scale = 1.0 / math.sqrt(d)
    pad = (-s) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)), constant_values=-1.0)
    nc = (s + pad) // chunk
    qg = q.reshape(bz, t, hkv, g, d).astype(jnp.float32)
    do = dout.reshape(bz, t, hkv, g, d).astype(jnp.float32)
    o32 = out.reshape(bz, t, hkv, g, d).astype(jnp.float32)
    delta = jnp.sum(do * o32, axis=-1)                    # (B,T,Hkv,G)
    delta = delta.transpose(0, 2, 3, 1)                   # (B,Hkv,G,T)

    kc = jnp.moveaxis(k.reshape(bz, nc, chunk, hkv, d), 1, 0)
    vc = jnp.moveaxis(v.reshape(bz, nc, chunk, hkv, d), 1, 0)
    pc = jnp.moveaxis(k_pos.reshape(bz, nc, chunk), 1, 0)

    dq0 = jnp.zeros((bz, t, hkv, g, d), jnp.float32)

    def body(dq, xs):
        kj, vj, pj = xs
        logits = jnp.einsum("btkgd,bskd->bkgts", qg * scale,
                            kj.astype(jnp.float32))
        valid = _chunk_valid(pj, q_pos, window, causal)
        logits = jnp.where(valid[:, None, None, :, :], logits, -1e30)
        p = jnp.exp(logits - lse[..., None])              # normalized probs
        dv_j = jnp.einsum("bkgts,btkgd->bskd", p, do)
        dp = jnp.einsum("btkgd,bskd->bkgts", do, vj.astype(jnp.float32))
        ds = p * (dp - delta[..., None]) * scale
        dq = dq + jnp.einsum("bkgts,bskd->btkgd", ds, kj.astype(jnp.float32))
        dk_j = jnp.einsum("bkgts,btkgd->bskd", ds, qg)
        return dq, (dk_j, dv_j)

    dq, (dks, dvs) = jax.lax.scan(body, dq0, (kc, vc, pc))
    dk = jnp.moveaxis(dks, 0, 1).reshape(bz, s + pad, hkv, d)[:, :s]
    dv = jnp.moveaxis(dvs, 0, 1).reshape(bz, s + pad, hkv, d)[:, :s]
    return (dq.reshape(bz, t, hq, d).astype(q.dtype), dk.astype(k.dtype),
            dv.astype(v.dtype), jnp.zeros_like(q_pos), jnp.zeros_like(k_pos))


_flash.defvjp(_flash_fwd, _flash_bwd)


def _attend_chunked(q, k, v, cfg: ModelConfig, q_pos, k_pos,
                    window: Optional[int], causal: bool,
                    chunk: int = ATTN_KV_CHUNK):
    return _flash(q, k, v, q_pos.astype(jnp.float32),
                  k_pos.astype(jnp.float32), window, causal, chunk)


def attend_positions(q, k, v, cfg: ModelConfig, q_pos, k_pos,
                     window: Optional[int], causal: bool):
    """Dispatcher: direct einsum for small S, chunked flash beyond."""
    s = k.shape[1]
    if s <= ATTN_CHUNK_THRESHOLD:
        mask = (k_pos[:, None, :] >= 0)
        if causal:
            mask = mask & (k_pos[:, None, :] <= q_pos[:, :, None])
        if window is not None:
            mask = mask & (k_pos[:, None, :] > q_pos[:, :, None] - window)
        return _attend(q, k, v, mask[:, None, None, :, :], cfg)
    return _attend_chunked(q, k, v, cfg, q_pos, k_pos, window, causal)


def apply_attention(params, x, cfg: ModelConfig, positions):
    """Full-sequence attention (training / prefill compute).

    x: (B, T, d_model); positions: (B, T). Masking (causal / sliding
    window / bidirectional) is derived from positions and cfg — the (T,S)
    mask is never materialized globally.
    """
    b, t, _ = x.shape
    h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    x = constrain(x, cfg, "btd")
    q = constrain(apply_dense(params["wq"], x).reshape(b, t, h, hd), cfg, "bthd")
    k = apply_dense(params["wk"], x).reshape(b, t, hkv, hd)
    v = apply_dense(params["wv"], x).reshape(b, t, hkv, hd)
    q = rope_rotate(q, positions, cfg.rope_theta)
    k = rope_rotate(k, positions, cfg.rope_theta)
    out = attend_positions(q, k, v, cfg, positions, positions,
                           cfg.sliding_window, cfg.causal)
    out = constrain(out, cfg, "bthd")
    return apply_dense(params["wo"], out.reshape(b, t, h * hd)), (k, v)


def init_kv_cache(cfg: ModelConfig, batch: int, seq_len: int, dtype):
    """KV cache for one layer. Sliding-window archs use a ring buffer of
    size `window` — this is what makes long_500k decode O(window).
    kv_quant: int8 payload + per-(token, head) f16 scales (EXPERIMENTS
    §Perf E): bytes/token drop from 2*D*2 to 2*D + 4."""
    size = seq_len if cfg.sliding_window is None else min(seq_len, cfg.sliding_window)
    shape = (batch, size, cfg.num_kv_heads, cfg.head_dim)
    if cfg.kv_quant:
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(shape[:3], jnp.float16),
                "v_scale": jnp.zeros(shape[:3], jnp.float16)}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _quantize_kv(x):
    """x: (B, 1, Hkv, D) -> (int8 payload, f16 per-(token,head) scale)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)     # (B,1,Hkv)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float16)


def _dequantize_kv(q, scale, dtype):
    return (q.astype(jnp.float32) * scale.astype(jnp.float32)[..., None]
            ).astype(dtype)


def _attend_quant(q, kq, ks, vq, vs, mask, cfg: ModelConfig):
    """Decode attention directly on the int8 cache: per-(token, head)
    scales fold into the logits / probs instead of materializing a
    dequantized cache copy (halves decode HBM traffic — §Perf iter E)."""
    b, t, hq, d = q.shape
    hkv = kq.shape[2]
    g = hq // hkv
    qg = (q.reshape(b, t, hkv, g, d).astype(jnp.float32)
          * (1.0 / math.sqrt(d)))
    logits = jnp.einsum("btkgd,bskd->bkgts", qg, kq.astype(jnp.float32))
    logits = logits * ks.astype(jnp.float32).transpose(0, 2, 1)[:, :, None, None, :]
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    probs = probs * vs.astype(jnp.float32).transpose(0, 2, 1)[:, :, None, None, :]
    out = jnp.einsum("bkgts,bskd->btkgd", probs, vq.astype(jnp.float32))
    return out.reshape(b, t, hq, d).astype(q.dtype)


def apply_attention_decode(params, x, cache, index, cfg: ModelConfig):
    """Single-token decode step.

    x: (B, 1, d_model); cache: {"k","v"} ring buffers (B, S_c, Hkv, D);
    index: scalar int32 — number of tokens already in the cache.
    Returns (out (B,1,d), new_cache).
    """
    b = x.shape[0]
    h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    s_c = cache["k"].shape[1]
    pos = jnp.full((b, 1), index, dtype=jnp.int32)
    q = apply_dense(params["wq"], x).reshape(b, 1, h, hd)
    k = apply_dense(params["wk"], x).reshape(b, 1, hkv, hd)
    v = apply_dense(params["wv"], x).reshape(b, 1, hkv, hd)
    q = rope_rotate(q, pos, cfg.rope_theta)
    k = rope_rotate(k, pos, cfg.rope_theta)

    slot = jnp.mod(index, s_c)  # ring-buffer write position
    if cfg.kv_quant:
        kq, ks = _quantize_kv(k)
        vq, vs = _quantize_kv(v)
        new_cache = {
            "k": jax.lax.dynamic_update_slice_in_dim(cache["k"], kq, slot, 1),
            "v": jax.lax.dynamic_update_slice_in_dim(cache["v"], vq, slot, 1),
            "k_scale": jax.lax.dynamic_update_slice_in_dim(
                cache["k_scale"], ks, slot, 1),
            "v_scale": jax.lax.dynamic_update_slice_in_dim(
                cache["v_scale"], vs, slot, 1),
        }
    else:
        new_k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot, axis=1)
        new_v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot, axis=1)
        new_cache = {"k": new_k, "v": new_v}

    # validity: slot j holds absolute position p_j; attend iff p_j <= index
    # and (window) p_j > index - window. Ring algebra:
    j = jnp.arange(s_c)[None, :]                      # (1, S_c)
    wrapped = index + 1 > s_c
    # absolute position stored in slot j after the write:
    abs_pos = jnp.where(
        j <= slot, index - slot + j, index - slot + j - s_c
    )
    valid = (abs_pos >= 0) & (abs_pos <= index)
    if cfg.sliding_window is not None:
        valid = valid & (abs_pos > index - cfg.sliding_window)
    del wrapped
    mask = valid[:, None, None, None, :]              # (1,1,1,1,S_c)
    if cfg.kv_quant:
        out = _attend_quant(q, new_cache["k"], new_cache["k_scale"],
                            new_cache["v"], new_cache["v_scale"], mask, cfg)
    else:
        out = _attend(q, new_k, new_v, mask, cfg)
    out = apply_dense(params["wo"], out.reshape(b, 1, h * hd))
    return out, new_cache


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------

def init_mlp(key, cfg: ModelConfig, dtype, d_ff: Optional[int] = None):
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    depth_scale = 0.02 / math.sqrt(2.0 * cfg.num_layers)
    return {
        "gate": init_dense(ks[0], d, ff, dtype),
        "up": init_dense(ks[1], d, ff, dtype),
        "down": {"w": _normal(ks[2], (ff, d), dtype, depth_scale)},
    }


def apply_mlp(params, x, cfg: ModelConfig = None):
    h = jax.nn.silu(apply_dense(params["gate"], x)) * apply_dense(params["up"], x)
    if cfg is not None:
        h = constrain(h, cfg, "btf")
    return apply_dense(params["down"], h)


# ---------------------------------------------------------------------------
# embeddings / unembedding
# ---------------------------------------------------------------------------

def init_embedding(key, cfg: ModelConfig, dtype):
    p = {"embed": _normal(key, (cfg.vocab_size, cfg.d_model), dtype)}
    if not cfg.tie_embeddings:
        p["unembed"] = _normal(jax.random.fold_in(key, 1),
                               (cfg.d_model, cfg.vocab_size), dtype)
    return p


def embed_tokens(params, tokens, cfg: ModelConfig):
    return jnp.take(params["embed"], tokens, axis=0)


def unembed(params, x, cfg: ModelConfig):
    if cfg.tie_embeddings:
        logits = jnp.einsum("...d,vd->...v", x, params["embed"])
    else:
        logits = x @ params["unembed"]
    return (logits * cfg.logit_scale).astype(jnp.float32)
