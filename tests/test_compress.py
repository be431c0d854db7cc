"""Compressed cohort payloads: sparsification + error feedback + int8
slot storage + the gather-superpose-decompress kernel.

Claims under test:

* error feedback is EXACT bookkeeping: ``residual + scatter(transmitted)
  == original`` bit-for-bit in f32, for both top-k and random-mask
  supports (hypothesis property over shapes/seeds);
* int8 stochastic rounding is unbiased: the dequantized mean over many
  counter keys converges to the input;
* the Pallas gather-superpose kernel (interpret mode), the jnp twin in
  ``ops.gather_superpose``, and the dense reference oracle agree on
  non-divisible shapes, with and without the int8 scale fold, and the
  varsigma they emit is the RAW sum of b*p;
* ``compressed_round_stats`` equals the dense stats computed on the
  scattered reconstructions;
* the driver-level EF hand-off is invariant under slot permutation: the
  (K,) state plane and the parked (K, s) residual planes advance
  bit-identically, the global model allclose.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp_compat import given, settings, st

from repro.core import ChannelConfig, SchedulerConfig
from repro.core.compress import (dequantize_int8, ef_residual, gather_rows,
                                 quantize_int8_stochastic, randmask_indices,
                                 scatter_rows, sparsify, topk_support)
from repro.data.partition import partition_noniid
from repro.data.pipeline import build_federation
from repro.data.synthetic import make_mnist_like
from repro.fl import FLClient, FusedPAOTA, PAOTAConfig
from repro.kernels.aircomp_sum import gather_superpose_pallas
from repro.kernels.ref import gather_superpose_ref
from repro.kernels.round_stats import compressed_round_stats
from repro.kernels.tiling import SCATTER_BLOCK_BYTES
from repro.models.mlp import init_mlp_params, mlp_loss


def _plane(seed, m, d):
    return jax.random.normal(jax.random.PRNGKey(seed), (m, d), jnp.float32)


# ---------------------------------------------------------------------------
# error feedback is exact bookkeeping (hypothesis property)
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000), st.integers(1, 6), st.integers(2, 64),
       st.sampled_from(["topk", "randmask"]))
def test_residual_plus_transmitted_is_original(seed, m, d, scheme):
    s = max(1, d // 3)
    comp = _plane(seed, m, d)
    if scheme == "topk":
        idx = topk_support(comp, s)
    else:
        idx = jnp.broadcast_to(
            randmask_indices(jax.random.PRNGKey(seed + 1), d, s), (m, s))
    v = gather_rows(comp, idx)
    e = ef_residual(comp, idx, v)
    recon = np.asarray(e + scatter_rows(v, idx, d))
    # EXACT: the residual is the in-place f32 complement, not a subtraction
    # of a rebuilt plane — bit-for-bit equality is the contract
    np.testing.assert_array_equal(recon, np.asarray(comp))


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000))
def test_residual_exact_through_int8(seed):
    """The residual absorbs the quantization error too: with int8 slot
    storage the complement is taken against the DEQUANTIZED values, so
    residual + scatter(dequant(q)) still reconstructs exactly."""
    m, d, s = 3, 48, 12
    comp = _plane(seed, m, d)
    idx = topk_support(comp, s)
    v = gather_rows(comp, idx)
    q, scale = quantize_int8_stochastic(v, jax.random.PRNGKey(seed + 7))
    v_hat = dequantize_int8(q, scale)
    e = ef_residual(comp, idx, v_hat)
    recon = np.asarray(e + scatter_rows(v_hat, idx, d))
    np.testing.assert_array_equal(recon, np.asarray(comp))


def test_sparsify_keeps_largest():
    e = jnp.asarray([[0.0, -5.0, 1.0, 3.0, -0.5]])
    vals, idx = sparsify(e, 2)
    assert set(np.asarray(idx)[0].tolist()) == {1, 3}
    np.testing.assert_array_equal(
        np.asarray(scatter_rows(vals, idx, 5))[0],
        np.asarray([0.0, -5.0, 0.0, 3.0, 0.0]))


# ---------------------------------------------------------------------------
# int8 stochastic rounding: unbiased, bounded error
# ---------------------------------------------------------------------------

def test_int8_stochastic_rounding_unbiased():
    m, s, n_keys = 4, 64, 400
    v = _plane(3, m, s)
    base = jax.random.PRNGKey(42)

    def dequant(i):
        q, scale = quantize_int8_stochastic(v, jax.random.fold_in(base, i))
        return dequantize_int8(q, scale)

    mean = np.mean(jax.vmap(dequant)(jnp.arange(n_keys)), axis=0)
    scale = np.abs(np.asarray(v)).max(axis=1, keepdims=True) / 127.0
    # one draw errs < scale; the mean of n_keys unbiased draws concentrates
    np.testing.assert_allclose(mean, np.asarray(v), atol=float(scale.max()) * 0.2)


def test_int8_rounding_error_bounded_by_one_step():
    v = _plane(5, 2, 128)
    q, scale = quantize_int8_stochastic(v, jax.random.PRNGKey(0))
    err = np.abs(np.asarray(dequantize_int8(q, scale)) - np.asarray(v))
    assert (err <= np.asarray(scale)[:, None] * (1 + 1e-6)).all()


# ---------------------------------------------------------------------------
# gather-superpose kernel vs twin vs dense oracle
# ---------------------------------------------------------------------------

# (m, d, s, block bytes): d and s off the 128 grid; the compressed
# cohort cell's shape; small blocks that split the (H, 128) aggregate into
# several stripes and the slot plane into ragged row and lane blocks
_SMALL = (5, 1000, 37, SCATTER_BLOCK_BYTES)
_CELL = (64, 8070, 504, SCATTER_BLOCK_BYTES)
_STRIPES = (40, 8070, 504, 16 << 10)
_RAGGED = (37, 1000, 300, 16 << 10)


def _compressed_cohort(shape, dtype, with_scale):
    """Slot rows on a top-k support that also holds coordinates 0 and
    d - 1 and, in every slot, one shared coordinate (collisions sum)."""
    m, d, s, _ = shape
    key = jax.random.PRNGKey(9)
    comp = _plane(11, m, d)
    idx = topk_support(comp, s)
    idx = idx.at[:, 0].set(d // 3).at[0, 1].set(0).at[m - 1, 2].set(d - 1)
    vals = gather_rows(comp, idx)
    scale = None
    if with_scale:
        vals, scale = quantize_int8_stochastic(vals, key)
    else:
        vals = vals.astype(jnp.dtype(dtype))
    bp = jax.random.uniform(jax.random.fold_in(key, 1), (m,), jnp.float32)
    noise = jax.random.normal(jax.random.fold_in(key, 2), (d,), jnp.float32)
    return vals, idx, bp, noise, scale


@pytest.mark.parametrize("dtype,with_scale,shape", [
    pytest.param("float32", False, _SMALL, id="float32-False"),
    pytest.param("bfloat16", False, _SMALL, id="bfloat16-False"),
    pytest.param("int8", True, _SMALL, id="int8-True"),
    pytest.param("int8", True, _CELL, id="int8-cell"),
    pytest.param("float32", False, _STRIPES, id="float32-stripes"),
    pytest.param("bfloat16", False, _STRIPES, id="bfloat16-stripes"),
    pytest.param("int8", True, _STRIPES, id="int8-stripes"),
    pytest.param("float32", False, _RAGGED, id="float32-ragged"),
    pytest.param("int8", True, _RAGGED, id="int8-ragged")])
def test_gather_superpose_matches_reference(dtype, with_scale, shape):
    d, block_bytes = shape[1], shape[3]
    vals, idx, bp, noise, scale = _compressed_cohort(shape, dtype,
                                                     with_scale)
    agg_ref, vs_ref = gather_superpose_ref(vals, idx, bp, noise, d,
                                           scale=scale)
    agg_k, vs_k = gather_superpose_pallas(vals, idx, bp, noise, d=d,
                                          scale=scale,
                                          block_bytes=block_bytes,
                                          interpret=True)
    from repro.kernels import ops
    agg_t, vs_t = ops.gather_superpose(vals, idx, bp, noise, d=d,
                                       scale=scale)
    # varsigma is the RAW sum of b*p — the int8 scale must NOT leak in
    np.testing.assert_allclose(float(vs_k), float(np.sum(np.asarray(bp))),
                               rtol=1e-6)
    np.testing.assert_allclose(float(vs_t), float(vs_ref), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(agg_k), np.asarray(agg_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(agg_t), np.asarray(agg_ref),
                               rtol=1e-5, atol=1e-5)


def test_gather_superpose_with_every_slot_masked_is_the_noise():
    """bp = 0 on every slot: each stripe keeps its noise, normalized by
    vs_min, and the raw varsigma is 0."""
    d = _STRIPES[1]
    vals, idx, _, noise, scale = _compressed_cohort(_STRIPES, "int8", True)
    bp = jnp.zeros((_STRIPES[0],), jnp.float32)
    agg, vs = gather_superpose_pallas(vals, idx, bp, noise, d=d,
                                      scale=scale, vs_min=1e-3,
                                      block_bytes=_STRIPES[3],
                                      interpret=True)
    assert float(vs) == 0.0
    np.testing.assert_array_equal(
        np.asarray(agg), np.asarray(noise) / np.float32(1e-3))


def test_gather_superpose_masked_rows_do_not_contribute():
    m, d, s = 4, 300, 16
    comp = _plane(13, m, d)
    idx = topk_support(comp, s)
    vals = gather_rows(comp, idx)
    bp = jnp.asarray([0.7, 0.0, 1.3, 0.0])      # rows 1 and 3 masked out
    noise = jnp.zeros((d,), jnp.float32)
    agg, vs = gather_superpose_ref(vals, idx, bp, noise, d)
    dense = np.asarray(scatter_rows(vals, idx, d))
    want = (0.7 * dense[0] + 1.3 * dense[2]) / 2.0
    np.testing.assert_allclose(np.asarray(agg), want, rtol=1e-6, atol=1e-7)
    assert float(vs) == pytest.approx(2.0)


# ---------------------------------------------------------------------------
# compressed round stats vs dense stats on the reconstructions
# ---------------------------------------------------------------------------

def test_compressed_round_stats_match_dense():
    m, d, s = 6, 500, 50
    comp = _plane(17, m, d)
    idx = topk_support(comp, s)
    vals = gather_rows(comp, idx)
    resid = ef_residual(comp, idx, vals)
    r_vals, r_idx = sparsify(resid, s)
    g = jax.random.normal(jax.random.PRNGKey(23), (d,), jnp.float32)
    dots, dn2, pn2, gn2 = compressed_round_stats(vals, idx, r_vals, r_idx,
                                                 g)
    dense_v = np.asarray(scatter_rows(vals, idx, d))
    dense_r = np.asarray(scatter_rows(r_vals, r_idx, d))
    g_np = np.asarray(g)
    np.testing.assert_allclose(np.asarray(dots),
                               (dense_v + dense_r) @ g_np, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(dn2),
                               (dense_v ** 2).sum(1) + (dense_r ** 2).sum(1),
                               rtol=1e-5)
    # pn2 is the TRANSMITTED energy only — what constraint (7) caps
    np.testing.assert_allclose(np.asarray(pn2), (dense_v ** 2).sum(1),
                               rtol=1e-5)
    np.testing.assert_allclose(float(gn2), float(g_np @ g_np), rtol=1e-6)


# ---------------------------------------------------------------------------
# driver-level EF hand-off: slot-permutation invariance
# ---------------------------------------------------------------------------

K = 8


@functools.lru_cache(maxsize=1)
def _ef_fixture():
    """A mid-flight compressed cohort carry (f32 slots — int8 dither is
    position-keyed, so only the exactly-stored dtypes are permutation
    invariant) + a non-donating one-step runner."""
    x, y, _, _ = make_mnist_like(n_train=1500, n_test=10)
    parts = partition_noniid(y, n_clients=K, seed=0)
    clients = [FLClient(d, mlp_loss, batch_size=32, lr=0.1, local_steps=5)
               for d in build_federation(x, y, parts)]
    srv = FusedPAOTA(init_mlp_params(jax.random.PRNGKey(0)), clients,
                     ChannelConfig(), SchedulerConfig(n_clients=K, seed=1),
                     PAOTAConfig(transmit="delta"), cohort_size=4,
                     compress="topk", compress_ratio=0.25, donate=False)
    srv.advance(3)
    step = lambda c: srv._jit_scan(c, srv.engine._x, srv.engine._y,
                                   n_rounds=1)
    return srv, step


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 100_000))
def test_ef_handoff_invariant_under_slot_permutation(seed):
    srv, step = _ef_fixture()
    carry = srv._carry
    perm = jnp.asarray(np.random.default_rng(seed).permutation(4))
    permuted = carry._replace(
        slot_client=carry.slot_client[perm],
        slot_live=carry.slot_live[perm],
        deltas=carry.deltas[perm],
        slot_idx=carry.slot_idx[perm],
        slot_resid=carry.slot_resid[perm],
        slot_resid_idx=carry.slot_resid_idx[perm])
    c1, o1 = step(carry)
    c2, o2 = step(permuted)
    # (K,) state plane: bit-identical
    for f in ("ready", "busy_lat", "model_round"):
        np.testing.assert_array_equal(np.asarray(getattr(c1, f)),
                                      np.asarray(getattr(c2, f)))
    # parked residuals index by CLIENT id, not slot: the hand-off scatter
    # lands each departing slot's residual on the same (K, s) row whatever
    # order the slots sit in — bit-identical
    np.testing.assert_array_equal(np.asarray(c1.resid_val),
                                  np.asarray(c2.resid_val))
    s1 = set(np.asarray(c1.slot_client)[np.asarray(c1.slot_live)].tolist())
    s2 = set(np.asarray(c2.slot_client)[np.asarray(c2.slot_live)].tolist())
    assert s1 == s2
    np.testing.assert_allclose(np.asarray(c1.global_vec),
                               np.asarray(c2.global_vec),
                               rtol=1e-4, atol=1e-5)
    assert float(o1["n_participants"][0]) == \
        pytest.approx(float(o2["n_participants"][0]))
