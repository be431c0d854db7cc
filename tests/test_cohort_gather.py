"""The cohort's minibatch gather: one row gather out of the data plane.

Cohort slots train on minibatches gathered straight from the padded
(K, n_max, ...) plane, ``x[ids, idx]`` (``BatchedEngine._train_gathered``),
where the driver once gathered each slot's whole client row and then each
step's batch from it. Both forms read the same samples and run the same
SGD, so the trained rows must agree bit for bit. The federation is ragged
(no client size a multiple of 8, some smaller than the batch), and the
cases cover homogeneous clients, per-client step counts and per-client
batch sizes, on the fused driver and on the sharded one (2 shards, with
a phantom client).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import ChannelConfig, SchedulerConfig
from repro.core.scheduler import ScenarioConfig
from repro.data.pipeline import ClientData
from repro.fl import FLClient, FusedPAOTA, PAOTAConfig
from repro.models.mlp import init_mlp_params, mlp_loss

SIZES = (13, 21, 37, 45, 5, 30, 17, 50, 23)       # K = 9, all ragged
SCENARIOS = {
    "homogeneous": None,
    "het_steps": ScenarioConfig(het_steps=(1, 2, 3)),
    "het_batch": ScenarioConfig(het_steps=(1, 3), het_batch=(3, 5, 8)),
}


@functools.lru_cache(maxsize=1)
def _data():
    rng = np.random.default_rng(7)
    return [(rng.normal(size=(n, 784)).astype(np.float32),
             rng.integers(0, 10, n).astype(np.int32)) for n in SIZES]


def _driver(sharded, scenario, **kw):
    clients = [FLClient(ClientData(x, y, i), mlp_loss, batch_size=8,
                        lr=0.1, local_steps=3)
               for i, (x, y) in enumerate(_data())]
    cls = FusedPAOTA
    if sharded:
        from repro.fl import ShardedPAOTA
        from repro.launch.mesh import make_cpu_mesh
        cls = ShardedPAOTA
        kw["mesh"] = make_cpu_mesh(data=2, model=1)
    return cls(init_mlp_params(jax.random.PRNGKey(0)), clients,
               ChannelConfig(), SchedulerConfig(n_clients=len(SIZES),
                                                seed=1),
               PAOTAConfig(), cohort_size=4, scenario=SCENARIOS[scenario],
               **kw)


def _two_gathers(srv, g, x, y, r, rows, gids):
    """The former cohort training: the slots' whole client rows first,
    then each step's minibatch from those rows."""
    eng = srv.engine
    idx = eng.round_plan(r, client_ids=gids, n_samples=eng._n_dev[gids])
    steps = eng.steps_for(gids)
    if srv.params_mode == "pytree":
        return eng._train_all_tree(g, x[rows], y[rows], idx, steps)
    return eng._train_all(srv.unravel(g), x[rows], y[rows], idx, steps)


def _global(srv):
    """A broadcast model away from the initial one."""
    g = srv._init_global
    return jax.tree_util.tree_map(lambda a: a * 1.5 + 0.01, g)


def _assert_rows_equal(new, old):
    for a, b in zip(jax.tree_util.tree_leaves(new),
                    jax.tree_util.tree_leaves(old)):
        assert a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("params_mode", ["raveled", "pytree"])
@pytest.mark.parametrize("scenario", list(SCENARIOS))
def test_fused_gather_trains_the_rows_of_two_gathers(scenario, params_mode):
    srv = _driver(False, scenario, params_mode=params_mode)
    x, y, g = srv.engine._x, srv.engine._y, _global(srv)
    ids = jnp.asarray([7, 4, 0, 8], jnp.uint32)      # client 4: n < B
    for r in (0, 3):
        new = jax.jit(srv._cohort_train)(g, x, y, r, ids)
        old = jax.jit(functools.partial(_two_gathers, srv))(
            g, x, y, r, ids, ids)
        _assert_rows_equal(new, old)


@pytest.mark.multidevice
@pytest.mark.parametrize("scenario", list(SCENARIOS))
def test_sharded_gather_trains_the_rows_of_two_gathers(scenario):
    from conftest import require_host_devices
    from repro.fl.sharded import vary_over
    require_host_devices(2)
    srv = _driver(True, scenario)
    assert srv.n_phantom == 1
    axes = srv.client_axes
    x, y, g = srv.engine._x, srv.engine._y, _global(srv)
    # shard-LOCAL slot rows: shard 0 holds clients 0-4, shard 1 clients
    # 5-8 and the phantom
    ids = jnp.asarray([4, 1, 3, 0], jnp.int32)
    r = 2

    def new_body(v, xs, ys, rows):
        return srv._shard_streams(srv._shard_offset()).cohort_train(
            v, xs, ys, r, rows)

    def old_body(v, xs, ys, rows):
        v = vary_over(v, axes)
        gids = (srv._shard_offset().astype(jnp.uint32)
                + rows.astype(jnp.uint32))
        return _two_gathers(srv, v, xs, ys, r, rows, gids)

    def run(body):
        smap = jax.shard_map(body, mesh=srv.mesh,
                             in_specs=(P(), srv._x_spec, srv._y_spec,
                                       P(axes)),
                             out_specs=P(axes))
        return jax.jit(smap)(g, x, y, ids)

    new, old = run(new_body), run(old_body)
    _assert_rows_equal(new, old)
    # and each slot row is its global client's own training, as the
    # fused driver computes it
    fused = _driver(False, scenario)
    gids = jnp.asarray([4, 1, 8, 5], jnp.uint32)
    _assert_rows_equal(new, jax.jit(fused._cohort_train)(
        g, fused.engine._x, fused.engine._y, r, gids))
