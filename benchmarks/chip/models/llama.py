"""Llama-architecture language-model clients (RMSNorm, rotary attention
with grouped KV heads, SwiGLU MLP, tied embeddings), configured by the
Hugging Face keys of the configuration file: weights from the seed, the
program's loss, the plain reference loss, and the training operation
count.

Parameters are the program's layout (``repro.models.transformer``): the
embedding, a final norm, and the layers stacked along a leading axis.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _sizes(cfg):
    d, h, kv = (cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"])
    hd = cfg.get("head_dim") or d // h
    return d, h, kv, hd, cfg["intermediate_size"], cfg["num_hidden_layers"]


def shapes(cfg):
    """The parameter tree's shapes, in the program's layout."""
    d, h, kv, hd, ff, n_layers = _sizes(cfg)
    lay = lambda *s: (n_layers,) + s
    return {
        "embedding": {"embed": (cfg["vocab_size"], d)},
        "final_norm": {"scale": (d,)},
        "layers": {
            "attn": {"wq": {"w": lay(d, h * hd)}, "wk": {"w": lay(d, kv * hd)},
                     "wv": {"w": lay(d, kv * hd)},
                     "wo": {"w": lay(h * hd, d)}},
            "ln1": {"scale": lay(d)}, "ln2": {"scale": lay(d)},
            "mlp": {"gate": {"w": lay(d, ff)}, "up": {"w": lay(d, ff)},
                    "down": {"w": lay(ff, d)}}}}


def init(key, cfg):
    """normal(0, 0.02) weights, depth-scaled output projections, unit norm
    scales: one jitted call on the device, float32."""
    tree = shapes(cfg)
    paths = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda s: isinstance(s, tuple))[0]
    out_scale = 0.02 / math.sqrt(2.0 * cfg["num_hidden_layers"])

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(paths))
        leaves = []
        for k, (path, shape) in zip(keys, paths):
            names = [p.key for p in path]
            if names[-1] == "scale":
                leaves.append(jnp.ones(shape, jnp.float32))
            else:
                std = out_scale if names[-2] in ("wo", "down") else 0.02
                leaves.append(std * jax.random.normal(k, shape, jnp.float32))
        treedef = jax.tree_util.tree_structure(
            tree, is_leaf=lambda s: isinstance(s, tuple))
        return jax.tree_util.tree_unflatten(treedef, leaves)
    return make(key)


def program_loss(cfg):
    """The loss the program's clients train: ``repro.models.transformer``
    built from this configuration's sizes."""
    from repro.models.config import ModelConfig
    from repro.models.transformer import loss_fn
    d, h, kv, hd, ff, n_layers = _sizes(cfg)
    mc = ModelConfig(name="bench", family="dense", num_layers=n_layers,
                     d_model=d, num_heads=h, num_kv_heads=kv, head_dim=hd,
                     d_ff=ff, vocab_size=cfg["vocab_size"],
                     rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"],
                     tie_embeddings=cfg["tie_word_embeddings"])

    def loss(params, batch):
        return loss_fn(params, {"tokens": batch["x"]}, mc)[0]
    return loss


def _rms(x, scale, eps):
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * scale


def _rope(x, theta):
    """Rotate (B, T, H, D) by position: the first and second halves of D
    are the pair's two coordinates."""
    t, half = x.shape[1], x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freq
    cos = jnp.cos(ang)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[None, :, None, :].astype(x.dtype)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def ref_loss(params, tokens, _labels, cfg, mm):
    """Plain next-token cross-entropy over ``tokens`` (B, T); ``mm`` is the
    matmul at the reference's precision and dtype."""
    d, h, kv, hd, ff, _ = _sizes(cfg)
    eps = cfg["rms_norm_eps"]
    dt = mm.dtype
    embed = params["embedding"]["embed"].astype(dt)
    x = embed[tokens]
    b, t = tokens.shape
    causal = jnp.tril(jnp.ones((t, t), bool))

    def layer(x, p):
        p = jax.tree_util.tree_map(lambda a: a.astype(dt), p)
        a = _rms(x, p["ln1"]["scale"], eps)
        q = _rope(mm(a, p["attn"]["wq"]["w"]).reshape(b, t, h, hd),
                  cfg["rope_theta"])
        k = _rope(mm(a, p["attn"]["wk"]["w"]).reshape(b, t, kv, hd),
                  cfg["rope_theta"])
        v = mm(a, p["attn"]["wv"]["w"]).reshape(b, t, kv, hd)
        k = jnp.repeat(k, h // kv, axis=2)
        v = jnp.repeat(v, h // kv, axis=2)
        s = mm.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
            jnp.asarray(hd, dt))
        s = jnp.where(causal, s, jnp.asarray(-1e30, s.dtype))
        att = mm.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
        x = x + mm(att.reshape(b, t, h * hd), p["attn"]["wo"]["w"])
        a = _rms(x, p["ln2"]["scale"], eps)
        gated = jax.nn.silu(mm(a, p["mlp"]["gate"]["w"])) * mm(
            a, p["mlp"]["up"]["w"])
        return x + mm(gated, p["mlp"]["down"]["w"]), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = _rms(x, params["final_norm"]["scale"].astype(dt), eps)
    logits = mm.einsum("btd,vd->btv", x, embed).astype(jnp.float32)
    logits, targets = logits[:, :-1], tokens[:, 1:]
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)


def step_flops(cfg, traffic) -> float:
    """Forward and backward operations of one local step: 6 per parameter
    per token (the tied embedding counted once, as the output projection)
    plus 12 * layers * T * heads * head_dim per token for the attention
    scores and values over the whole T x T square."""
    d, h, kv, hd, ff, n_layers = _sizes(cfg)
    n_params = (cfg["vocab_size"] * d + d + n_layers * (
        d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * ff + 2 * d))
    t = traffic["data"]["seq_len"]
    per_token = 6.0 * n_params + 12.0 * n_layers * t * h * hd
    return per_token * t * traffic["batch"]
