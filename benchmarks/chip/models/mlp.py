"""The paper's MLP client (arXiv:2305.04066 Sec. IV-A): weights from the
seed, the program's loss, the plain reference loss, and the training
operation count.

Parameters are the program's layout: ``{"l1": {"w", "b"}, "l2": ..., "l3":
...}``, input -> hidden -> hidden -> classes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _dims(cfg):
    return ([cfg["d_in"]] + [cfg["hidden"]] * cfg["n_hidden_layers"]
            + [cfg["n_classes"]])


def init(key, cfg):
    """He-normal weights and zero biases, one jitted call on the device."""
    dims = _dims(cfg)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(dims) - 1)
        return {f"l{i + 1}": {
            "w": jax.random.normal(keys[i], (dims[i], dims[i + 1]),
                                   jnp.float32) * jnp.sqrt(2.0 / dims[i]),
            "b": jnp.zeros((dims[i + 1],), jnp.float32)}
            for i in range(len(dims) - 1)}
    return make(key)


def program_loss(cfg):
    """The loss the program's clients train: ``repro.models.mlp``."""
    if cfg["n_hidden_layers"] != 2:
        raise ValueError("the program's MLP has exactly two hidden layers")
    from repro.models.mlp import mlp_loss
    return mlp_loss


def ref_loss(params, x, y, cfg, mm):
    """Plain cross-entropy of the MLP; ``mm`` is the matmul at the
    reference's precision."""
    n = len(_dims(cfg)) - 1
    h = x
    for i in range(n):
        layer = params[f"l{i + 1}"]
        h = mm(h, layer["w"]) + layer["b"]
        if i < n - 1:
            h = jax.nn.relu(h)
    logits = h.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - picked)


def step_flops(cfg, traffic) -> float:
    """Forward and backward operations of one local step: 6 per parameter
    per sample (2 forward, 4 backward)."""
    dims = _dims(cfg)
    n_params = sum(a * b + b for a, b in zip(dims[:-1], dims[1:]))
    return 6.0 * n_params * traffic["batch"]
