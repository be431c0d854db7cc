"""Pytree-native PAOTA round core.

The federated model is an arbitrary params pytree; the raveled federation
is its single-leaf instance. Pinned here:

* pytree-vs-raveled equivalence — the MLP federated as its natural 4-leaf
  (3 layers x {w, b}) params tree is allclose to the raveled fused
  reference round for round (identical RNG draws — latency, channel,
  minibatch plans, and ONE flat AWGN realization split across leaves —
  float reduction regrouping across leaves the only difference), fused
  AND sharded;
* phantom-pad invariance — a K the client-axis extent does not divide
  pads with masked phantom clients and reproduces the unsharded
  trajectory draw for draw;
* a transformer-config client federation (minicpm-2b reduced) completes
  sharded PAOTA rounds on the forced 8-device mesh with its params
  carried natively (leaves placed by stack_client_specs).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ChannelConfig, SchedulerConfig
from repro.data.partition import partition_noniid
from repro.data.pipeline import ClientData, build_federation
from repro.data.synthetic import make_mnist_like
from repro.fl import FLClient, FusedPAOTA, PAOTAConfig, ShardedPAOTA
from repro.models.mlp import init_mlp_params, mlp_loss

K = 8


@pytest.fixture(scope="module")
def data():
    x, y, _, _ = make_mnist_like(n_train=2000, n_test=10)
    parts = partition_noniid(y, n_clients=K, seed=0)
    return x, y, parts


def _clients(data, k=None):
    x, y, parts = data
    if k is not None:
        parts = partition_noniid(y, n_clients=k, seed=0)
    return [FLClient(d, mlp_loss, batch_size=32, lr=0.1, local_steps=5)
            for d in build_federation(x, y, parts)]


def _params():
    return init_mlp_params(jax.random.PRNGKey(0))


def _cfg(k, **kw):
    return (ChannelConfig(), SchedulerConfig(n_clients=k, seed=1, **kw),
            PAOTAConfig())


# ---------------------------------------------------------------------------
# tree helper units
# ---------------------------------------------------------------------------

def test_tree_scalars_match_raveled():
    """client norms / dots / cosines over a multi-leaf stacked tree equal
    the raveled single-leaf computation (same model, different leaf
    split)."""
    from jax.flatten_util import ravel_pytree

    from repro.core.power_control import (client_dots, client_sq_norms,
                                          cosine_similarity)
    key = jax.random.PRNGKey(0)
    tree = {"a": jax.random.normal(key, (6, 3, 4)),
            "b": jax.random.normal(jax.random.fold_in(key, 1), (6, 5))}
    vec = {"a": jax.random.normal(jax.random.fold_in(key, 2), (3, 4)),
           "b": jax.random.normal(jax.random.fold_in(key, 3), (5,))}
    flat = jnp.stack([ravel_pytree(
        jax.tree_util.tree_map(lambda l: l[i], tree))[0] for i in range(6)])
    gvec = ravel_pytree(vec)[0]
    np.testing.assert_allclose(np.asarray(client_sq_norms(tree)),
                               np.asarray(client_sq_norms(flat)), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(client_dots(tree, vec)),
                               np.asarray(client_dots(flat, gvec)),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(cosine_similarity(tree, vec)),
                               np.asarray(cosine_similarity(flat, gvec)),
                               rtol=1e-5, atol=1e-6)


def test_tree_aggregate_noise_is_leaf_split_invariant():
    """paota_aggregate_stacked draws ONE flat AWGN realization: the
    multi-leaf aggregate equals the raveled aggregate bit-for-bit modulo
    the per-leaf reduction split (same noise, same normalizer)."""
    from jax.flatten_util import ravel_pytree

    from repro.core.aggregation import paota_aggregate_stacked
    key = jax.random.PRNGKey(7)
    tree = {"a": jax.random.normal(key, (5, 4)),
            "b": jax.random.normal(jax.random.fold_in(key, 1), (5, 2, 3))}
    flat = jnp.stack([ravel_pytree(
        jax.tree_util.tree_map(lambda l: l[i], tree))[0] for i in range(5)])
    powers = jnp.asarray([1.0, 0.5, 2.0, 0.0, 3.0])
    mask = jnp.asarray([1.0, 1.0, 0.0, 1.0, 1.0])
    nkey = jax.random.PRNGKey(11)
    agg_t, vs_t = paota_aggregate_stacked(tree, powers, mask, nkey, 0.3)
    agg_f, vs_f = paota_aggregate_stacked(flat, powers, mask, nkey, 0.3)
    assert float(vs_t) == pytest.approx(float(vs_f), rel=1e-6)
    np.testing.assert_allclose(np.asarray(ravel_pytree(agg_t)[0]),
                               np.asarray(agg_f), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("key_kind", ["raw", "typed", "rbg"])
def test_leaf_noise_draw_equals_flat_split(key_kind):
    """The superposition's noise, drawn leaf by leaf in each leaf's own
    shape, is the flat draw's split bit for bit: the AWGN realization
    does not depend on how the model is split into leaves. A key whose
    draw is not a hash of the flat index (``rbg``) takes the flat draw."""
    from repro.core.aggregation import _counter_draw, stacked_tree_noise
    key = {"raw": jax.random.fold_in(jax.random.PRNGKey(5), 3),
           "typed": jax.random.key(5),
           "rbg": jax.random.key(5, impl="rbg")}[key_kind]
    leaves = [jnp.zeros((2, 3, 40, 57)), jnp.zeros((2, 1000)),
              jnp.zeros((2, 7, 5)), jnp.zeros((2, 2, 3, 4, 5))]
    assert _counter_draw(key, 8000) == (key_kind != "rbg")
    flat = jax.jit(lambda k: 0.3 * jax.random.normal(k, (8000,)))(key)
    mine = jax.jit(lambda k: stacked_tree_noise(k, leaves, 0.3))(key)
    off = 0
    for leaf, b in zip(leaves, mine):
        size = int(np.prod(leaf.shape[1:]))
        a = flat[off:off + size].reshape(leaf.shape[1:])
        off += size
        assert a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# fused pytree mode (single device)
# ---------------------------------------------------------------------------

def test_pytree_fused_matches_raveled_over_rounds(data):
    """Acceptance: the MLP federated as its params pytree is allclose to
    the raveled fused reference round for round over 4 rounds."""
    rav = FusedPAOTA(_params(), _clients(data), *_cfg(K))
    tre = FusedPAOTA(_params(), _clients(data), *_cfg(K),
                     params_mode="pytree")
    assert len(jax.tree_util.tree_leaves(tre.global_params())) >= 4
    for rf, rt in zip(rav.advance(4), tre.advance(4)):
        assert rf["n_participants"] == rt["n_participants"]
        assert rf["time"] == rt["time"]
        assert rf["varsigma"] == pytest.approx(rt["varsigma"], rel=1e-5)
        np.testing.assert_allclose(rav.global_vec, tre.global_vec,
                                   rtol=1e-4, atol=1e-5)


def test_pytree_fused_zero_uploader_holds_global(data):
    """The zero-uploader guard holds every leaf bit-identical."""
    tre = FusedPAOTA(_params(), _clients(data), ChannelConfig(),
                     SchedulerConfig(n_clients=K, seed=1, delta_t=8.0,
                                     lat_lo=30.0, lat_hi=40.0),
                     PAOTAConfig(), params_mode="pytree")
    g0 = tre.global_vec.copy()
    rows = tre.advance(3)
    assert all(r["n_participants"] == 0 for r in rows)
    np.testing.assert_array_equal(tre.global_vec, g0)


def test_fused_rejects_unknown_params_mode(data):
    with pytest.raises(ValueError, match="params_mode"):
        FusedPAOTA(_params(), _clients(data), *_cfg(K), params_mode="tree")


# ---------------------------------------------------------------------------
# sharded pytree mode + phantom padding (forced 8-device mesh)
# ---------------------------------------------------------------------------

@pytest.mark.multidevice
def test_pytree_sharded_matches_raveled_fused(data, client_mesh_8):
    """Acceptance: the pytree MLP federation, sharded over the 8-device
    client mesh (stack_client_specs placement with cfg=None), is allclose
    to the raveled single-device fused reference over 4 rounds."""
    rav = FusedPAOTA(_params(), _clients(data), *_cfg(K))
    tre = ShardedPAOTA(_params(), _clients(data), *_cfg(K),
                       mesh=client_mesh_8, params_mode="pytree")
    for rf, rt in zip(rav.advance(4), tre.advance(4)):
        assert rf["n_participants"] == rt["n_participants"]
        assert rf["varsigma"] == pytest.approx(rt["varsigma"], rel=1e-5)
    np.testing.assert_allclose(rav.global_vec, tre.global_vec,
                               rtol=1e-4, atol=1e-5)


@pytest.mark.multidevice
@pytest.mark.parametrize("params_mode", ["raveled", "pytree"])
def test_phantom_pad_invariance(data, client_mesh_8, params_mode):
    """K=10 on 8 shards (pad to 16 with 6 phantoms) reproduces the K=10
    unsharded fused trajectory draw for draw: phantoms are never ready,
    never power, and never enter a psum or metric."""
    k = 10
    fused = FusedPAOTA(_params(), _clients(data, k), *_cfg(k))
    shard = ShardedPAOTA(_params(), _clients(data, k), *_cfg(k),
                         mesh=client_mesh_8, params_mode=params_mode)
    assert (shard.k, shard.k_pad, shard.n_phantom, shard.k_local) \
        == (10, 16, 6, 2)
    for rf, rs in zip(fused.advance(5), shard.advance(5)):
        assert rf["n_participants"] == rs["n_participants"]
        assert rf["time"] == rs["time"]
        assert rf["mean_staleness"] == pytest.approx(rs["mean_staleness"],
                                                     rel=1e-5)
        assert rf["varsigma"] == pytest.approx(rs["varsigma"], rel=1e-5)
    np.testing.assert_allclose(fused.global_vec, shard.global_vec,
                               rtol=1e-4, atol=1e-5)


@pytest.mark.multidevice
def test_pytree_sharded_rejects_nontrivial_model_axis(data):
    """A mesh whose non-client, non-TP axes have extent > 1 must refuse
    pytree mode, and the refusal must NAME the offending axis with its
    extent and point at every workaround — tp_axes (intra-client TP),
    raveled mode, or widening client_axes (the error is the only
    breadcrumb a launcher user gets)."""
    from tests.conftest import require_host_devices
    require_host_devices(8)
    from repro.launch.mesh import make_cpu_mesh
    mesh = make_cpu_mesh(data=4, model=2)
    with pytest.raises(NotImplementedError, match="non-client") as exc:
        ShardedPAOTA(_params(), _clients(data), *_cfg(K), mesh=mesh,
                     params_mode="pytree")
    msg = str(exc.value)
    assert "'model' (extent 2)" in msg
    assert "tp_axes" in msg
    assert "params_mode='raveled'" in msg
    assert "client_axes" in msg


@pytest.mark.multidevice
@pytest.mark.slow
def test_transformer_client_sharded_round(client_mesh_8):
    """Acceptance: a transformer-config client federation (minicpm-2b
    reduced) completes sharded PAOTA rounds on the forced 8-device CPU
    mesh with its params pytree placed by stack_client_specs."""
    from repro.configs.minicpm_2b import REDUCED as cfg
    from repro.launch.mesh import client_axes_for
    from repro.models.transformer import init_model, loss_fn

    k, n, seq = 8, 8, 16
    rng = np.random.default_rng(0)

    def tloss(p, batch):
        return loss_fn(p, {"tokens": batch["x"]}, cfg)[0]

    clients = [FLClient(ClientData(
        rng.integers(0, cfg.vocab_size, (n, seq)).astype(np.int32),
        np.zeros(n, np.int32), i), tloss, batch_size=4, lr=0.01,
        local_steps=2) for i in range(k)]
    params = init_model(jax.random.PRNGKey(0), cfg)
    srv = ShardedPAOTA(params, clients, ChannelConfig(),
                       SchedulerConfig(n_clients=k, seed=1), PAOTAConfig(),
                       mesh=client_mesh_8, params_mode="pytree",
                       model_cfg=cfg)
    assert srv.client_axes == client_axes_for(cfg, srv.mesh)
    rows = srv.advance(3)
    assert any(r["n_participants"] > 0 for r in rows)
    g = srv.global_params()
    assert jax.tree_util.tree_structure(g) \
        == jax.tree_util.tree_structure(params)
    assert all(bool(jnp.isfinite(l).all())
               for l in jax.tree_util.tree_leaves(g))
    # tokens stacked with their integer dtype (stack_federation keeps it)
    assert srv.engine._x.dtype == jnp.int32
