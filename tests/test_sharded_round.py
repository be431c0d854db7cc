"""Mesh-sharded PAOTA round: the shard_map'd scan must reproduce the
single-device fused scan round for round (same counter streams, float
reduction order across shards the only difference), on an 8-virtual-device
CPU mesh (tests/conftest.py forces the devices)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ChannelConfig, SchedulerConfig
from repro.data.partition import partition_noniid
from repro.data.pipeline import build_federation
from repro.data.synthetic import make_mnist_like
from repro.fl import FLClient, FusedPAOTA, PAOTAConfig, ShardedPAOTA
from repro.models.mlp import init_mlp_params, mlp_loss

pytestmark = pytest.mark.multidevice

K = 8


@pytest.fixture(scope="module")
def data():
    x, y, _, _ = make_mnist_like(n_train=2000, n_test=10)
    parts = partition_noniid(y, n_clients=K, seed=0)
    return x, y, parts


def _clients(data):
    x, y, parts = data
    return [FLClient(d, mlp_loss, batch_size=32, lr=0.1, local_steps=5)
            for d in build_federation(x, y, parts)]


def _params():
    return init_mlp_params(jax.random.PRNGKey(0))


def test_sharded_matches_fused_over_rounds(data, client_mesh_8):
    """Acceptance: ShardedPAOTA on the 8-device mesh is allclose to the
    single-device FusedPAOTA round for round over >= 3 rounds — identical
    counter streams (latency, channel, noise, minibatch plans), psum'd
    AirComp vs single-device einsum."""
    fused = FusedPAOTA(_params(), _clients(data), ChannelConfig(),
                       SchedulerConfig(n_clients=K, seed=1), PAOTAConfig())
    shard = ShardedPAOTA(_params(), _clients(data), ChannelConfig(),
                         SchedulerConfig(n_clients=K, seed=1),
                         PAOTAConfig(), mesh=client_mesh_8)
    assert shard.n_shards == 8 and shard.k_local == 1
    for rf, rs in zip(fused.advance(4), shard.advance(4)):
        assert rf["n_participants"] == rs["n_participants"]
        assert rf["time"] == rs["time"]
        assert rf["mean_staleness"] == pytest.approx(rs["mean_staleness"],
                                                     rel=1e-5)
        assert rf["varsigma"] == pytest.approx(rs["varsigma"], rel=1e-5)
        np.testing.assert_allclose(fused.global_vec, shard.global_vec,
                                   rtol=1e-4, atol=1e-5)


def test_sharded_chunked_scan_parity(data, client_mesh_8):
    """Counter RNG is position-based: one 6-round scan and 3+3 chunked
    scans land on the same sharded trajectory."""
    one = ShardedPAOTA(_params(), _clients(data), ChannelConfig(),
                       SchedulerConfig(n_clients=K, seed=1),
                       PAOTAConfig(), mesh=client_mesh_8)
    two = ShardedPAOTA(_params(), _clients(data), ChannelConfig(),
                       SchedulerConfig(n_clients=K, seed=1),
                       PAOTAConfig(), mesh=client_mesh_8)
    rows = one.advance(6)
    two.advance(3)
    two.advance(3)
    assert any(r["n_participants"] > 0 for r in rows)
    np.testing.assert_allclose(one.global_vec, two.global_vec,
                               rtol=1e-5, atol=1e-6)


def test_sharded_pads_non_divisible_k_with_phantoms(client_mesh_8):
    """A client-axis extent that does not divide K pads the federation
    with masked phantom clients (never ready, zero power) instead of
    refusing; the padded run completes with only real participants.
    (Draw-for-draw invariance vs the unsharded run is pinned in
    tests/test_pytree_round.py.)"""
    x, y, _, _ = make_mnist_like(n_train=1500, n_test=10)
    parts = partition_noniid(y, n_clients=6, seed=0)
    clients = [FLClient(d, mlp_loss, batch_size=32, lr=0.1, local_steps=2)
               for d in build_federation(x, y, parts)]
    srv = ShardedPAOTA(_params(), clients, ChannelConfig(),
                       SchedulerConfig(n_clients=6, seed=1), PAOTAConfig(),
                       mesh=client_mesh_8)
    assert (srv.k, srv.k_pad, srv.n_phantom, srv.k_local) == (6, 8, 2, 1)
    rows = srv.advance(4)
    assert all(r["n_participants"] <= 6 for r in rows)
    assert any(r["n_participants"] > 0 for r in rows)
    assert np.isfinite(srv.global_vec).all()


def test_shard_aware_kernel_entries_match_reference(client_mesh_8):
    """The kernels' shard-aware entry points (aircomp psum reduction,
    shard-local cosines) inside shard_map equal the single-device
    reductions on the gathered arrays."""
    from jax.sharding import PartitionSpec as P

    from repro.core.power_control import cosine_similarity
    from repro.kernels.aircomp_sum import aircomp_sum_psum
    from repro.kernels.cosine_sim import cosine_sim_shard

    k, d = 16, 96
    key = jax.random.PRNGKey(3)
    stacked = jax.random.normal(key, (k, d), jnp.float32)
    bp = jax.random.uniform(jax.random.fold_in(key, 1), (k,))
    noise = jax.random.normal(jax.random.fold_in(key, 2), (d,))
    g = jax.random.normal(jax.random.fold_in(key, 3), (d,))

    def body(s, b, n, gg):
        agg, varsigma = aircomp_sum_psum(s, b, n, "data")
        cos = cosine_sim_shard(s, gg, "data")
        return agg, varsigma, cos

    smap = jax.jit(jax.shard_map(
        body, mesh=client_mesh_8,
        in_specs=(P("data"), P("data"), P(), P()),
        out_specs=(P(), P(), P("data"))))
    agg, varsigma, cos = smap(stacked, bp, noise, g)

    ref_vs = jnp.sum(bp)
    ref_agg = (jnp.einsum("k,kd->d", bp, stacked) + noise) / ref_vs
    np.testing.assert_allclose(np.asarray(varsigma), float(ref_vs), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(agg), np.asarray(ref_agg),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(cos),
                               np.asarray(cosine_similarity(stacked, g)),
                               rtol=1e-5, atol=1e-6)


def test_sharded_waterfill_matches_single_device(client_mesh_8):
    """P2 water-filling with psum'd grid reductions returns the same beta
    (each shard its slice) and objective as the single-device solve."""
    from jax.sharding import PartitionSpec as P

    from repro.core.boxqp import waterfill_beta_jnp

    k = 24
    rng = np.random.default_rng(0)
    rho = jnp.asarray(rng.uniform(0.2, 1.0, k), jnp.float32)
    theta = jnp.asarray(rng.uniform(0.0, 1.0, k), jnp.float32)
    p_max = jnp.full((k,), 15.0, jnp.float32)
    b = jnp.asarray((rng.random(k) < 0.7).astype(np.float32))
    c1, c0 = 8.0, 1e-4

    beta_ref, obj_ref = waterfill_beta_jnp(rho, theta, p_max, b, c1, c0)

    smap = jax.jit(jax.shard_map(
        lambda r, t, p, m: waterfill_beta_jnp(r, t, p, m, c1, c0,
                                              axis_name="data"),
        mesh=client_mesh_8,
        in_specs=(P("data"), P("data"), P("data"), P("data")),
        out_specs=(P("data"), P())))
    beta_sh, obj_sh = smap(rho, theta, p_max, b)

    # near the optimum the P2 objective is flat in tau, so the refined tau
    # (and thus beta) is only determined to ~sqrt(eps_f32) under a changed
    # reduction order; the objective itself pins much tighter
    np.testing.assert_allclose(np.asarray(beta_sh), np.asarray(beta_ref),
                               atol=2e-3)
    assert float(obj_sh) == pytest.approx(float(obj_ref), rel=1e-5)
