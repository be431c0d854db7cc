"""Delta-plane tests: fused round-stats + superpose-and-normalize
kernels vs the ref.py oracles and the jnp twins (interpret mode on CPU),
over rank-2 stripes and over leaves read in their own layout, the
chunked-jnp twin, bf16 pending storage error bounds, and donation
safety."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.aircomp_sum import superpose_normalize_pallas
from repro.kernels.round_stats import round_stats_jnp, round_stats_pallas

RNG = np.random.default_rng(7)


def _assert_stats_close(got, want, rtol=3e-5, atol=3e-4):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=rtol, atol=atol)


# ---------------------------------------------------------------------------
# round-stats kernel vs oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k,d", [(4, 64), (37, 1111), (100, 8070), (1, 513)])
@pytest.mark.parametrize("with_payload", [False, True])
def test_round_stats_kernel_sweep(k, d, with_payload):
    de = jnp.asarray(RNG.normal(size=(k, d)), jnp.float32)
    g = jnp.asarray(RNG.normal(size=d), jnp.float32)
    p = jnp.asarray(RNG.normal(size=(k, d)), jnp.float32) \
        if with_payload else None
    stats, gn2 = round_stats_pallas(de, g, p, interpret=True)
    want, wgn2 = ref.round_stats_ref(de, g, p)
    assert stats.shape == (k, 3 if with_payload else 2)
    _assert_stats_close(stats, want)
    assert float(gn2) == pytest.approx(float(wgn2), rel=3e-5)


@pytest.mark.parametrize("dtype", [jnp.bfloat16])
def test_round_stats_kernel_bf16_accumulates_f32(dtype):
    """bf16 storage in, f32 stats out — the kernel upcasts per stripe."""
    k, d = 16, 2048
    de = jnp.asarray(0.01 * RNG.normal(size=(k, d)), dtype)
    p = jnp.asarray(RNG.normal(size=(k, d)), dtype)
    g = jnp.asarray(RNG.normal(size=d), jnp.float32)
    stats, gn2 = round_stats_pallas(de, g, p, interpret=True)
    assert stats.dtype == jnp.float32
    want, _ = ref.round_stats_ref(de.astype(jnp.float32), g,
                                  p.astype(jnp.float32))
    _assert_stats_close(stats, want, rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("chunk", [None, 64, 1000])
def test_round_stats_jnp_chunked_matches_ref(chunk):
    """The chunked-jnp twin equals the oracle for chunk sizes below,
    at, and above the leaf size."""
    k, d = 13, 777
    de = jnp.asarray(RNG.normal(size=(k, d)), jnp.float32)
    p = jnp.asarray(RNG.normal(size=(k, d)), jnp.float32)
    g = jnp.asarray(RNG.normal(size=d), jnp.float32)
    dots, dn2, pn2, gn2 = round_stats_jnp(de, g, p, chunk=chunk)
    want, wgn2 = ref.round_stats_ref(de, g, p)
    _assert_stats_close(jnp.stack([dots, dn2, pn2], 1), want, rtol=1e-5)
    assert float(gn2) == pytest.approx(float(wgn2), rel=1e-5)


def test_round_stats_jnp_pytree_accumulates_leaves():
    """Tree stats == stats of the raveled concatenation (same model,
    different leaf split) up to float regrouping."""
    k = 9
    tree_d = {"a": (k, 33), "b": (k, 8, 16), "c": (k, 5)}
    de = {n: jnp.asarray(RNG.normal(size=s), jnp.float32)
          for n, s in tree_d.items()}
    g = {n: jnp.asarray(RNG.normal(size=s[1:]), jnp.float32)
         for n, s in tree_d.items()}
    dots, dn2, pn2, gn2 = round_stats_jnp(de, g, de)
    flat_de = jnp.concatenate(
        [l.reshape(k, -1) for l in jax.tree_util.tree_leaves(de)], 1)
    flat_g = jnp.concatenate(
        [l.reshape(-1) for l in jax.tree_util.tree_leaves(g)])
    want, wgn2 = ref.round_stats_ref(flat_de, flat_g, flat_de)
    _assert_stats_close(jnp.stack([dots, dn2, pn2], 1), want, rtol=1e-5)
    assert float(gn2) == pytest.approx(float(wgn2), rel=1e-5)
    np.testing.assert_allclose(np.asarray(dn2), np.asarray(pn2), rtol=1e-6)


# ---------------------------------------------------------------------------
# superpose-and-normalize kernel vs oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k,d", [(4, 64), (37, 1111), (100, 8070)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_superpose_normalize_sweep(k, d, dtype):
    x = jnp.asarray(RNG.normal(size=(k, d)), dtype)
    powers = jnp.asarray(RNG.random(k), jnp.float32)
    mask = jnp.asarray(RNG.random(k) < 0.6, jnp.float32)
    n = jnp.asarray(RNG.normal(size=d), jnp.float32)
    agg, vs = superpose_normalize_pallas(x, powers, mask, n, interpret=True)
    want, wvs = ref.superpose_normalize_ref(x, powers, mask, n)
    assert agg.dtype == jnp.float32
    tol = dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(np.asarray(agg), np.asarray(want), **tol)
    assert float(vs) == pytest.approx(float(wvs), abs=1e-6)


def test_superpose_normalize_masked_phantom_rows():
    """Masked (phantom) rows never leak into the aggregate, no matter how
    large their stale payload values are."""
    k, d = 8, 512
    x = jnp.asarray(RNG.normal(size=(k, d)), jnp.float32)
    x = x.at[3].set(1e30).at[6].set(-1e30)          # phantom garbage rows
    powers = jnp.ones((k,), jnp.float32)
    mask = jnp.asarray([1, 1, 0, 0, 1, 0, 0, 1], jnp.float32)
    n = jnp.zeros((d,), jnp.float32)
    agg, vs = superpose_normalize_pallas(x, powers, mask, n, interpret=True)
    want = (x[0] + x[1] + x[4] + x[7]) / 4.0
    assert float(vs) == pytest.approx(4.0)
    np.testing.assert_allclose(np.asarray(agg), np.asarray(want), rtol=2e-6,
                               atol=2e-6)


def test_superpose_normalize_zero_uploaders():
    """A zero-uploader period returns raw varsigma 0 (the guard signal)
    and a pure clamped-noise aggregate — the caller's guard discards it."""
    k, d = 5, 256
    x = jnp.asarray(RNG.normal(size=(k, d)), jnp.float32)
    powers = jnp.asarray(RNG.random(k), jnp.float32)
    mask = jnp.zeros((k,), jnp.float32)
    n = jnp.asarray(RNG.normal(size=d), jnp.float32)
    agg, vs = superpose_normalize_pallas(x, powers, mask, n, interpret=True)
    assert float(vs) == 0.0
    np.testing.assert_allclose(np.asarray(agg), np.asarray(n) / 1e-12,
                               rtol=1e-4)


# ---------------------------------------------------------------------------
# native-view kernels: leaves of rank >= 3 read in their own layout
# ---------------------------------------------------------------------------

# (stacked leaf shape, rows per block): rank 3 and 4 (and 5: L = 6 from
# two lead dims), C of 576, 192 and 128, S not a multiple of the block
# rows (a ragged last block), S below one row tile (one block read past
# the leaf's end), L > 1, K of 1, 4 and 100
NATIVE = {
    "k4_L2_S40_C576_tail8": ((4, 2, 40, 576), 16),
    "k4_S30_C576_one_block": ((4, 30, 576), 64),
    "k1_L3_S50_C192_tail2": ((1, 3, 50, 192), 16),
    "k100_S20_C128_tail4": ((100, 20, 128), 16),
    "k4_L6_S33_C128_rank5": ((4, 3, 2, 33, 128), 16),
}


def _block_bytes(shape, dtype, rows):
    """The ``block_bytes`` that gives ``rows`` rows per native block."""
    lanes = -(-shape[-1] // 128) * 128
    return rows * shape[0] * lanes * jnp.dtype(dtype).itemsize


@pytest.mark.parametrize("with_payload", [False, True])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", list(NATIVE))
def test_round_stats_native_leaf(case, dtype, with_payload):
    """Kernel over a leaf in its own shape == the jnp twin over the same
    leaf (flattened), at f32 rounding."""
    shape, rows = NATIVE[case]
    de = jnp.asarray(RNG.normal(size=shape), dtype)
    p = (jnp.asarray(RNG.normal(size=shape), dtype) if with_payload
         else None)
    g = jnp.asarray(RNG.normal(size=shape[1:]), jnp.float32)
    stats, gn2 = round_stats_pallas(
        de, g, p, block_bytes=_block_bytes(shape, dtype, rows),
        interpret=True)
    dots, dn2, pn2, wgn2 = round_stats_jnp(de, g, p)
    want = jnp.stack([dots, dn2] + ([pn2] if with_payload else []), 1)
    assert stats.shape == want.shape and stats.dtype == jnp.float32
    # two f32 summation orders over up to 46k terms: ~sqrt(n) eps apart.
    # The dots cancel, so their error scales with |delta| |g|.
    scale = jnp.maximum(jnp.sqrt(dn2 * wgn2)[:, None], 1.0)
    np.testing.assert_allclose(np.asarray(stats / scale),
                               np.asarray(want / scale), rtol=5e-5,
                               atol=5e-6)
    assert float(gn2) == pytest.approx(float(wgn2), rel=5e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", list(NATIVE))
def test_superpose_normalize_native_leaf(case, dtype):
    """Kernel over a leaf and its noise in their own shape == the
    superposition of the flattened leaf, written in the leaf's shape."""
    shape, rows = NATIVE[case]
    k = shape[0]
    x = jnp.asarray(RNG.normal(size=shape), dtype)
    powers = jnp.asarray(RNG.random(k), jnp.float32)
    mask = jnp.asarray(RNG.random(k) < 0.6, jnp.float32).at[0].set(1.0)
    n = jnp.asarray(RNG.normal(size=shape[1:]), jnp.float32)
    agg, vs = superpose_normalize_pallas(
        x, powers, mask, n, block_bytes=_block_bytes(shape, dtype, rows),
        interpret=True)
    want, wvs = ref.superpose_normalize_ref(x.reshape((k, -1)), powers,
                                            mask, n.reshape(-1))
    assert agg.shape == shape[1:] and agg.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(agg).reshape(-1),
                               np.asarray(want), rtol=1e-5, atol=1e-6)
    assert float(vs) == pytest.approx(float(wvs), rel=1e-6)


@pytest.mark.parametrize("k,d", [(4, 1000), (100, 8070)])
def test_stripe_kernels_mask_a_ragged_tail(k, d):
    """A rank-2 leaf in several lane stripes whose last one is ragged
    (masked in the kernel, no padded copy) == the oracles."""
    de = jnp.asarray(RNG.normal(size=(k, d)), jnp.float32)
    g = jnp.asarray(RNG.normal(size=d), jnp.float32)
    small = 8 * 512 * 4 if k <= 8 else 104 * 512 * 4    # 512-lane stripes
    stats, gn2 = round_stats_pallas(de, g, de, block_bytes=small,
                                    interpret=True)
    want, wgn2 = ref.round_stats_ref(de, g, de)
    _assert_stats_close(stats, want)
    assert float(gn2) == pytest.approx(float(wgn2), rel=3e-5)
    powers = jnp.asarray(RNG.random(k), jnp.float32)
    mask = jnp.ones((k,), jnp.float32)
    agg, _ = superpose_normalize_pallas(de, powers, mask, g,
                                        block_bytes=small, interpret=True)
    wagg, _ = ref.superpose_normalize_ref(de, powers, mask, g)
    np.testing.assert_allclose(np.asarray(agg), np.asarray(wagg),
                               rtol=3e-5, atol=3e-5)


def test_block_sizes_from_bytes():
    """Blocks carry about PLANE_BLOCK_BYTES of one plane, fit VMEM
    double-buffered, align to the row tile or to 128 lanes, and at large
    K a stripe is no wider than 512 lanes and never below 128."""
    from repro.kernels.tiling import (PLANE_BLOCK_BYTES, VMEM_BYTES,
                                      native_rows, stripe_lanes)
    bf, f32 = jnp.bfloat16, jnp.float32
    for s, c in [(1536, 576), (49152, 576), (576, 192), (576, 1536)]:
        b = native_rows(4, s, c, [bf, bf], [f32])
        lanes = -(-c // 128) * 128
        plane = 4 * b.size * lanes * 2
        assert b.size % 16 == 0 and (b.count - 1) * b.size + b.tail == s
        assert PLANE_BLOCK_BYTES // 2 < plane <= PLANE_BLOCK_BYTES
        assert 2 * (2 * plane + b.size * lanes * 4) <= VMEM_BYTES
    assert native_rows(4, 30, 576, [bf], [f32]) .size == 32   # one block
    assert native_rows(10 ** 4, 64, 4096, [f32], [f32]) is None
    for k in (1000, 4096, 10 ** 5):
        b = stripe_lanes(k, 10 ** 6, [f32, f32], [f32])
        assert b.size % 128 == 0 and 128 <= b.size <= 512
    assert stripe_lanes(4, 576, [bf], [f32]).size == 576     # whole leaf


# ---------------------------------------------------------------------------
# round-level: one-sweep factors == the composed stage ops
# ---------------------------------------------------------------------------

def test_round_factors_matches_composed_ops():
    from repro.core.power_control import (client_dots, client_sq_norms,
                                          cosine_similarity,
                                          similarity_factor,
                                          staleness_factor)
    from repro.fl.runtime import round_factors
    k, d = 23, 4097
    deltas = jnp.asarray(RNG.normal(size=(k, d)), jnp.float32)
    pending = jnp.asarray(RNG.normal(size=(k, d)), jnp.float32)
    g = jnp.asarray(RNG.normal(size=d), jnp.float32)
    prev = jnp.asarray(RNG.normal(size=d), jnp.float32)
    stal = jnp.asarray(RNG.integers(0, 5, k), jnp.float32)
    rho, theta, w2 = round_factors(deltas, pending, g, prev, stal, 3.0)
    cos = cosine_similarity(deltas, g - prev)
    np.testing.assert_allclose(np.asarray(theta),
                               np.asarray(similarity_factor(cos)), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(rho),
                               np.asarray(staleness_factor(stal, 3.0)),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w2),
                               np.asarray(client_sq_norms(pending)),
                               rtol=1e-6)
    # transmit='delta': payload norms must be the delta norms, not re-swept
    _, _, w2d = round_factors(deltas, None, g, prev, stal, 3.0)
    np.testing.assert_allclose(np.asarray(w2d),
                               np.asarray(client_sq_norms(deltas)),
                               rtol=1e-6)


def test_round_factors_zero_direction_gives_half_theta():
    """w_g == w_g^{t-1} (e.g. after a held round): cos must be exactly 0,
    theta exactly 1/2 — no NaN from the 0/0."""
    from repro.fl.runtime import round_factors
    k, d = 7, 129
    deltas = jnp.asarray(RNG.normal(size=(k, d)), jnp.float32)
    g = jnp.asarray(RNG.normal(size=d), jnp.float32)
    stal = jnp.zeros((k,), jnp.float32)
    rho, theta, _ = round_factors(deltas, None, g, g, stal, 3.0)
    np.testing.assert_array_equal(np.asarray(theta), 0.5)


# ---------------------------------------------------------------------------
# bf16 pending storage + donation safety (driver level)
# ---------------------------------------------------------------------------

def _tiny_server(pending_dtype="float32", donate=True, seed=0, k=12):
    from repro.core import ChannelConfig, SchedulerConfig
    from repro.data.partition import partition_noniid
    from repro.data.pipeline import build_federation
    from repro.data.synthetic import make_mnist_like
    from repro.fl import BatchedEngine, FusedPAOTA, PAOTAConfig
    from repro.models.mlp import init_mlp_params, mlp_loss
    x, y, _, _ = make_mnist_like(n_train=600, n_test=10, seed=1234)
    parts = partition_noniid(y, n_clients=k, sizes=(16, 24), seed=seed)
    fed = build_federation(x, y, parts, seed=seed)
    eng = BatchedEngine(fed, mlp_loss, batch_size=8, lr=0.1, local_steps=2)
    params = init_mlp_params(jax.random.PRNGKey(seed))
    return FusedPAOTA(params, eng, ChannelConfig(),
                      SchedulerConfig(n_clients=k, seed=seed),
                      PAOTAConfig(seed=seed), pending_dtype=pending_dtype,
                      donate=donate)


def test_bf16_pending_tracks_f32_trajectory():
    """Property: the bf16 storage cast is a RELATIVE rounding (~2^-8) of
    the stored planes, not a cancellation. After one aggregation the
    global must sit within a rounding-scaled envelope of the f32 result;
    over more rounds the trajectories drift (SGD amplifies the rounding)
    but must stay finite with identical participation patterns (the
    scheduler never sees the planes)."""
    f32 = _tiny_server("float32")
    b16 = _tiny_server("bfloat16")
    # first aggregation with >=1 uploader: one storage-rounding step
    rows_f, rows_b = f32.advance(2), b16.advance(2)
    gf, gb = f32.global_vec, b16.global_vec
    assert any(r["n_participants"] > 0 for r in rows_f)
    scale = float(np.max(np.abs(gf)))
    assert float(np.max(np.abs(gf - gb))) < 0.02 * scale
    rows_f, rows_b = f32.advance(4), b16.advance(4)
    for rf, rb in zip(rows_f, rows_b):
        assert rf["n_participants"] == rb["n_participants"]
        assert rf["time"] == rb["time"]
    assert np.isfinite(b16.global_vec).all()
    # the carry planes really are stored in bf16, the globals in f32
    assert b16._carry.pending.dtype == jnp.bfloat16
    assert b16._carry.deltas.dtype == jnp.bfloat16
    assert b16._carry.global_vec.dtype == jnp.float32


@pytest.mark.multidevice
def test_bf16_sharded_global_stays_f32(client_mesh_8):
    """The sharded psum aggregation must return f32 aggregates for a bf16
    carry — only the stored planes are rounded, never the global update
    (regression: the psum entries used to cast the aggregate back to the
    payload dtype, quantizing w_g to bf16 every round)."""
    from repro.core import ChannelConfig, SchedulerConfig
    from repro.data.partition import partition_noniid
    from repro.data.pipeline import build_federation
    from repro.data.synthetic import make_mnist_like
    from repro.fl import BatchedEngine, FusedPAOTA, PAOTAConfig, ShardedPAOTA
    from repro.models.mlp import init_mlp_params, mlp_loss

    def build(cls, **kw):
        x, y, _, _ = make_mnist_like(n_train=800, n_test=10, seed=1234)
        parts = partition_noniid(y, n_clients=16, sizes=(16, 24), seed=0)
        eng = BatchedEngine(build_federation(x, y, parts, seed=0), mlp_loss,
                            batch_size=8, lr=0.1, local_steps=2)
        return cls(init_mlp_params(jax.random.PRNGKey(0)), eng,
                   ChannelConfig(), SchedulerConfig(n_clients=16, seed=0),
                   PAOTAConfig(seed=0), pending_dtype="bfloat16", **kw)

    fused = build(FusedPAOTA)
    shard = build(ShardedPAOTA, mesh=client_mesh_8)
    rows_f, rows_s = fused.advance(4), shard.advance(4)
    assert any(r["n_participants"] > 0 for r in rows_f)
    for rf, rs in zip(rows_f, rows_s):
        assert rf["n_participants"] == rs["n_participants"]
    assert shard._carry.global_vec.dtype == jnp.float32
    assert shard._carry.pending.dtype == jnp.bfloat16
    gf, gs = fused.global_vec, shard.global_vec
    # full precision: NOT bf16-quantized (a bf16 roundtrip would be exact)
    assert not np.array_equal(
        gs, np.asarray(jnp.asarray(gs).astype(jnp.bfloat16).astype(
            jnp.float32)))
    np.testing.assert_allclose(gf, gs, rtol=2e-3, atol=2e-3)


def test_donation_safe():
    """Donating the round carry into the scan must not change a single
    bit of the trajectory (the donated buffers are never re-read)."""
    don = _tiny_server(donate=True)
    ref_srv = _tiny_server(donate=False)
    for _ in range(3):
        rd, rr = don.advance(2), ref_srv.advance(2)
        for a, b in zip(rd, rr):
            assert a == b, (a, b)
    np.testing.assert_array_equal(don.global_vec, ref_srv.global_vec)


def test_donation_buffers_actually_donated():
    """The scan jit really declares the carry donated (guards against the
    flag silently regressing to a copy)."""
    srv = _tiny_server(donate=True)
    srv.advance(1)
    carry = srv._carry
    srv.advance(1)
    # the old carry's buffers were handed to XLA; their jax view must be
    # marked deleted (donated), not silently copied
    assert carry.pending.is_deleted()
    assert carry.deltas.is_deleted()
