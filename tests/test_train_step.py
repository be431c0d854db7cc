"""Semantics of the SPMD PAOTA round step (launch.steps) on a 1x1 CPU mesh:
the aggregation must equal eq. (8) exactly, stragglers must keep their
local training state (eq. 4), and grad accumulation must not change the
SGD result."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.launch.shapes import InputShape
from repro.launch.steps import make_paota_train_step
from repro.models import init_model
from repro.models.transformer import loss_fn

pytestmark = pytest.mark.slow  # arch-zoo/serving/integration tier (scripts/ci.sh)


def _mesh11():
    from repro.launch.mesh import make_cpu_mesh
    return make_cpu_mesh(data=1, model=1)


def _setup(arch="smollm-135m", k=3, m=2, mb=2, seq=32, sigma=0.0):
    cfg = get_reduced(arch)
    shape = InputShape("t", seq_len=seq, global_batch=k * mb, kind="train")
    mesh = _mesh11()
    with jax.set_mesh(mesh):
        step, structs, _ = make_paota_train_step(
            cfg, mesh, shape, lr=0.05, local_steps=m,
            sigma_over_varsigma=sigma, client_axes=("data",), donate=False)
    params = init_model(jax.random.PRNGKey(0), cfg)
    stacked = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (1,) + x.shape), params)
    # K=1 on the 1x1 mesh; emulate K clients by running the pure function
    return cfg, shape, mesh, step, params


def test_round_step_aggregation_matches_eq8():
    """Run the un-jitted round math with K=3 clients and compare the masked
    power-weighted aggregate against a hand computation."""
    cfg = get_reduced("smollm-135m")
    k, m, mb, seq = 3, 2, 2, 32
    shape = InputShape("t", seq_len=seq, global_batch=k * mb, kind="train")
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (k, m, mb, seq)),
                       jnp.int32)
    powers = jnp.asarray([2.0, 3.0, 5.0], jnp.float32)
    mask = jnp.asarray([1.0, 0.0, 1.0], jnp.float32)   # client 1 straggles
    seed = jax.random.key_data(jax.random.PRNGKey(0)).astype(jnp.uint32)

    params = init_model(jax.random.PRNGKey(1), cfg)
    stacked = jax.tree_util.tree_map(
        lambda x: jnp.stack([x, x * 1.01, x * 0.99]), params)

    # reference semantics: manual per-client local SGD
    def local_sgd(p, mbs):
        for i in range(m):
            sub = {"tokens": mbs[i]}
            g = jax.grad(lambda q: loss_fn(q, sub, cfg)[0])(p)
            p = jax.tree_util.tree_map(lambda a, b: a - 0.05 * b, p, g)
        return p

    trained = [local_sgd(jax.tree_util.tree_map(lambda x: x[i], stacked),
                         toks[i]) for i in range(k)]
    bp = np.asarray(powers * mask)
    varsigma = bp.sum()

    def agg(*leaves):
        return sum(b * l for b, l in zip(bp, leaves)) / varsigma

    expected_agg = jax.tree_util.tree_map(agg, *trained)
    # validate the aggregation rule (eq. 8) against the stacked form used
    # by the jitted step:
    from repro.core.aggregation import paota_aggregate_stacked
    flat_trained = [jax.flatten_util.ravel_pytree(t)[0] for t in trained]
    stacked_vec = jnp.stack(flat_trained)
    got, vs = paota_aggregate_stacked(stacked_vec, powers, mask,
                                      jax.random.PRNGKey(0), 0.0)
    want_vec = jax.flatten_util.ravel_pytree(expected_agg)[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want_vec),
                               rtol=2e-5, atol=2e-5)
    assert float(vs) == pytest.approx(float(varsigma))


def test_jitted_round_step_runs_and_improves_loss():
    cfg, shape, mesh, step, params = _setup()
    k, m, mb, seq = 1, 2, 6, 32
    rng = np.random.default_rng(0)
    stacked = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (1,) + x.shape), params)
    shape1 = InputShape("t", seq_len=seq, global_batch=mb, kind="train")
    with jax.set_mesh(mesh):
        step1, structs, _ = make_paota_train_step(
            cfg, mesh, shape1, lr=0.05, local_steps=m,
            sigma_over_varsigma=0.0, client_axes=("data",), donate=False)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, m, mb, seq)),
                       jnp.int32)
    powers = jnp.ones((1,), jnp.float32)
    mask = jnp.ones((1,), jnp.float32)
    seed = jax.random.key_data(jax.random.PRNGKey(0)).astype(jnp.uint32)
    losses = []
    with jax.set_mesh(mesh):
        for r in range(4):
            stacked, metrics = step1(stacked, {"tokens": toks}, powers, mask,
                                     seed)
            losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]
    assert metrics["participants"] == 1


def test_grad_accum_equivalent_to_full_batch():
    """accum chunks of the same batch must produce (nearly) the same SGD
    update as the unchunked step (bf16 accumulation tolerance)."""
    cfg = get_reduced("olmo-1b")
    rng = np.random.default_rng(1)
    mb, seq = 8, 64
    toks = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (mb, seq)),
                                  jnp.int32)}
    params = init_model(jax.random.PRNGKey(0), cfg)
    g_full = jax.grad(lambda p: loss_fn(p, toks, cfg)[0])(params)

    accum = 4
    sub = jax.tree_util.tree_map(
        lambda x: x.reshape((accum, mb // accum) + x.shape[1:]), toks)
    g_acc = jax.tree_util.tree_map(jnp.zeros_like, params)
    for i in range(accum):
        chunk = jax.tree_util.tree_map(lambda x: x[i], sub)
        g = jax.grad(lambda p: loss_fn(p, chunk, cfg)[0])(params)
        g_acc = jax.tree_util.tree_map(lambda a, b: a + b, g_acc, g)
    g_acc = jax.tree_util.tree_map(lambda x: x / accum, g_acc)
    err = max(float(jnp.max(jnp.abs(a - b)))
              for a, b in zip(jax.tree_util.tree_leaves(g_full),
                              jax.tree_util.tree_leaves(g_acc)))
    assert err < 5e-3
