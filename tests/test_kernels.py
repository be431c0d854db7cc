"""Per-kernel validation: shape/dtype sweeps, assert_allclose against the
ref.py pure-jnp oracles (kernels run in interpret mode on CPU)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.aircomp_sum import superpose_normalize_pallas
from repro.kernels.cosine_sim import cosine_partials_pallas
from repro.kernels.swa_attention import swa_attention_pallas

RNG = np.random.default_rng(42)


def _aircomp_sum(x, bp, n):
    """The raveled AirComp sum on the TPU path (``ops.aircomp_sum``): the
    superposition kernel with the mask all ones, interpreted."""
    return superpose_normalize_pallas(x, bp, jnp.ones_like(bp), n,
                                      interpret=True)[0]


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("k,d", [(4, 64), (37, 1111), (100, 8070), (1, 513)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_aircomp_sum_sweep(k, d, dtype):
    x = jnp.asarray(RNG.normal(size=(k, d)), dtype)
    bp = jnp.asarray(RNG.random(k), jnp.float32)
    n = jnp.asarray(RNG.normal(size=d), dtype)
    got = _aircomp_sum(x, bp, n)
    want = ref.aircomp_sum_ref(x, bp, n)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_aircomp_sum_bf16_payload_f32_aggregate():
    """Regression: a bf16 payload must come back as an f32 aggregate with
    the AWGN joining the f32 accumulator UN-rounded. The kernel wrapper
    used to cast the noise to the payload dtype and emit the aggregate in
    it, so a bf16 carry re-rounded the received y (the global update plane)
    to 8 mantissa bits every round."""
    k, d = 24, 1111
    x32 = jnp.asarray(RNG.normal(size=(k, d)), jnp.float32)
    x = x32.astype(jnp.bfloat16)
    bp = jnp.asarray(RNG.random(k), jnp.float32)
    n = jnp.asarray(RNG.normal(size=d), jnp.float32)
    got = _aircomp_sum(x, bp, n)
    assert got.dtype == jnp.float32
    # oracle on the SAME rounded payload but full-precision noise path: the
    # only error left is the bf16 storage rounding of x, not of the output
    want = ref.aircomp_sum_ref(x.astype(jnp.float32), bp, n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_aircomp_sum_masked_clients_ignored():
    x = jnp.asarray(RNG.normal(size=(8, 256)), jnp.float32)
    bp = jnp.asarray([1.0, 0, 2.0, 0, 0, 0.5, 0, 0], jnp.float32)
    n = jnp.zeros(256, jnp.float32)
    got = _aircomp_sum(x, bp, n)
    want = (1.0 * x[0] + 2.0 * x[2] + 0.5 * x[5]) / 3.5
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-6,
                               atol=2e-6)


@pytest.mark.parametrize("k,d", [(3, 128), (50, 2048), (100, 8070)])
@pytest.mark.parametrize("block_d", [128, 512])
def test_cosine_partials_sweep(k, d, block_d):
    x = jnp.asarray(RNG.normal(size=(k, d)), jnp.float32)
    g = jnp.asarray(RNG.normal(size=d), jnp.float32)
    got = cosine_partials_pallas(x, g, block_d=block_d, interpret=True)
    want = ref.cosine_partials_ref(x, g)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-4)


@pytest.mark.parametrize("t,s,d,window,causal,bq,bk", [
    (128, 128, 64, None, True, 64, 64),
    (200, 200, 32, 64, True, 64, 64),
    (256, 256, 64, 96, True, 128, 64),
    (256, 256, 128, 128, True, 128, 128),
    (64, 64, 16, None, False, 32, 32),     # encoder (bidirectional)
    (96, 96, 64, 32, True, 32, 32),
    (130, 130, 64, 64, True, 64, 64),      # non-multiple seq (padding path)
])
def test_swa_attention_sweep(t, s, d, window, causal, bq, bk):
    q = jnp.asarray(RNG.normal(size=(3, t, d)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(3, s, d)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(3, s, d)), jnp.float32)
    got = swa_attention_pallas(q, k, v, window=window, causal=causal,
                               block_q=bq, block_k=bk, interpret=True)
    want = ref.swa_attention_ref(q, k, v, window=window, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("dtype", [jnp.bfloat16])
def test_swa_attention_bf16(dtype):
    q = jnp.asarray(RNG.normal(size=(2, 128, 64)), dtype)
    k = jnp.asarray(RNG.normal(size=(2, 128, 64)), dtype)
    v = jnp.asarray(RNG.normal(size=(2, 128, 64)), dtype)
    got = swa_attention_pallas(q, k, v, window=64, block_q=64, block_k=64,
                               interpret=True)
    want = ref.swa_attention_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                                 v.astype(jnp.float32), window=64)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=3e-2,
                               atol=3e-2)


def test_swa_window_exploits_structure():
    """The windowed kernel must visit O(window/block) kv stripes per query
    block, not O(T/block) — check the grid arithmetic (perf contract)."""
    window, bq, bk, t = 96, 64, 64, 4096
    n_j = (window + bq) // bk + 1
    assert n_j == 3
    assert n_j < t // bk  # much fewer stripes than full attention


def test_model_attention_matches_kernel():
    """GQA path in models.layers vs the Pallas kernel wrapper."""
    from repro.kernels.ops import swa_attention
    from repro.models import layers as L
    from repro.configs import get_reduced
    cfg = get_reduced("smollm-135m")
    rng = np.random.default_rng(0)
    b, t = 2, 96
    h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = jnp.asarray(rng.normal(size=(b, t, h, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, t, hkv, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, t, hkv, hd)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
    mask = L.causal_window_mask(pos, pos, None)[:, None, None]
    want = L._attend(q, k, v, mask, cfg)
    got = swa_attention(q, k, v, window=None, causal=True,
                        block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------------------
# Mamba2 SSD intra-chunk kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("g,q,n,d", [(4, 32, 16, 32), (8, 64, 128, 64),
                                     (2, 256, 64, 64), (3, 128, 64, 32)])
def test_ssd_intra_chunk_sweep(g, q, n, d):
    from repro.kernels.ref import ssd_intra_chunk_ref
    from repro.kernels.ssd_chunk import ssd_intra_chunk_pallas
    rng = np.random.default_rng(g + q)
    cum = -jnp.asarray(np.cumsum(0.05 + 0.2 * rng.random((g, q)),
                                 axis=1).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(g, q, n)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(g, q, n)), jnp.float32)
    xdt = jnp.asarray(rng.normal(size=(g, q, d)), jnp.float32)
    got = ssd_intra_chunk_pallas(cum, b, c, xdt, interpret=True)
    want = ssd_intra_chunk_ref(cum, b, c, xdt)
    for a, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(w),
                                   rtol=2e-5, atol=2e-5)


def test_ssd_chunked_kernel_backend_matches_jnp():
    """Full SSD forward with the Pallas intra-chunk backend must equal the
    pure-jnp path (and therefore the naive recurrence)."""
    import dataclasses
    from repro.configs import get_reduced
    from repro.models.ssm import ssd_chunked
    cfg = dataclasses.replace(get_reduced("mamba2-370m"), ssm_chunk=16)
    rng = np.random.default_rng(5)
    bz, t, h, p, g, n = 2, 49, 4, 8, 1, 16
    x = jnp.asarray(rng.normal(size=(bz, t, h, p)), jnp.float32)
    dt = jnp.asarray(0.1 + 0.5 * rng.random((bz, t, h)), jnp.float32)
    a = -jnp.asarray(0.5 + rng.random(h), jnp.float32)
    B = jnp.asarray(rng.normal(size=(bz, t, g, n)), jnp.float32)
    C = jnp.asarray(rng.normal(size=(bz, t, g, n)), jnp.float32)
    y0, s0 = ssd_chunked(x, dt, a, B, C, cfg, use_kernel=False)
    y1, s1 = ssd_chunked(x, dt, a, B, C, cfg, use_kernel=True)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(s0), np.asarray(s1),
                               rtol=2e-5, atol=2e-5)
