"""Fault-tolerant round core (ISSUE-10 tentpole): injection, containment,
rollback, resume.

The contracts pinned here:

* identity — a default ``FaultConfig()`` (and screen/divergence off) is
  the IDENTITY on both the fused and sharded drivers: every fault stage
  is skipped at trace time, so the trajectory is bit-identical to a
  driver built with no fault arguments at all;
* containment — a screened faulty round equals the same round in which
  the faulty clients' uploads were dropped in transit (the scenario
  drop mask): screening masks corrupt rows out of the superposition
  exactly like phantoms, bit-for-bit;
* NaN storms stall, screening rides through — unscreened non-finite
  uploads are stopped by the aggregate finite guard (w_g freezes,
  finite), while the screened run keeps converging on the clean cohort;
* Byzantine uploads corrupt, the norm fence contains — finite divergent
  deltas sail past the finite guard and blow up ||w_g|| unscreened; the
  ``screen_max_norm`` fence (or the divergence rollback) bounds them;
* kill-at-round-r + restore == the uninterrupted run bit-for-bit, on
  every carry layout (fused dense, fused compressed cohort, sharded
  dense, sharded grouped with a pod blackout);
* the compiled sharded program keeps exactly ONE cross-client
  model-sized all-reduce per round with screening enabled.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ChannelConfig, SchedulerConfig
from repro.core.scheduler import FaultConfig
from repro.data.partition import partition_noniid
from repro.data.pipeline import build_federation
from repro.data.synthetic import make_mnist_like
from repro.fl import FLClient, FusedPAOTA, PAOTAConfig
from repro.models.mlp import init_mlp_params, mlp_loss

K = 8
# fast latencies: every client uploads every period, so faults reach the
# superposition from their start round on
FAST_SCHED = dict(n_clients=K, delta_t=8.0, lat_lo=0.5, lat_hi=3.0)


@pytest.fixture(scope="module")
def data():
    x, y, _, _ = make_mnist_like(n_train=2000, n_test=10)
    parts = partition_noniid(y, n_clients=K, seed=0)
    return x, y, parts


def _clients(data):
    x, y, parts = data
    return [FLClient(d, mlp_loss, batch_size=32, lr=0.1, local_steps=2)
            for d in build_federation(x, y, parts)]


def _params():
    return init_mlp_params(jax.random.PRNGKey(0))


def _fused(data, transmit="delta", **kw):
    return FusedPAOTA(_params(), _clients(data), ChannelConfig(),
                      SchedulerConfig(seed=1, **FAST_SCHED),
                      PAOTAConfig(transmit=transmit), **kw)


def _sharded(data, mesh, transmit="delta", **kw):
    from repro.fl import ShardedPAOTA
    return ShardedPAOTA(_params(), _clients(data), ChannelConfig(),
                        SchedulerConfig(seed=1, **FAST_SCHED),
                        PAOTAConfig(transmit=transmit), mesh=mesh, **kw)


# ---------------------------------------------------------------------------
# identity: FaultConfig() + screen off + divergence off == no fault args
# ---------------------------------------------------------------------------

def test_identity_faultconfig_is_noop_fused(data):
    plain = _fused(data)
    armed = _fused(data, faults=FaultConfig(), screen=False,
                   divergence_factor=0.0)
    for rp, ra in zip(plain.advance(3), armed.advance(3)):
        assert rp == ra
    np.testing.assert_array_equal(plain.global_vec, armed.global_vec)


@pytest.mark.multidevice
def test_identity_faultconfig_is_noop_sharded(data, client_mesh_8):
    plain = _sharded(data, client_mesh_8)
    armed = _sharded(data, client_mesh_8, faults=FaultConfig(),
                     screen=False, divergence_factor=0.0)
    for rp, ra in zip(plain.advance(3), armed.advance(3)):
        assert rp == ra
    np.testing.assert_array_equal(plain.global_vec, armed.global_vec)


# ---------------------------------------------------------------------------
# containment: screened corrupt rows == uploads dropped in transit
# ---------------------------------------------------------------------------

def test_screened_faulty_round_equals_dropped_uploads(data):
    """Acceptance: a round with faulty clients under screening produces a
    global BIT-identical to the same round in which those clients' uploads
    were lost in transit (scenario drop mask) — screening masks the rows
    out of the superposition exactly like phantoms: b zeroed, payload row
    exact +0.0, scalars sanitized, and the restart/broadcast state plane
    untouched."""
    F = jnp.array([1, 4])                        # the always-faulty clients
    screened = _fused(data, screen=True)
    base = screened._streams()

    def poisoned_train(g, x, y, r):
        tr = base.local_train(g, x, y, r)
        return jax.tree_util.tree_map(
            lambda l: l.at[F].set(jnp.nan), tr)

    screened._streams = lambda: base._replace(local_train=poisoned_train)

    dropped = _fused(data)                       # clean train, no screening
    base_d = dropped._streams()
    drop = jnp.zeros((K,), bool).at[F].set(True)
    dropped._streams = lambda: base_d._replace(
        scenario=lambda t: (jnp.ones((K,), bool), drop))

    for rs, rd in zip(screened.advance(4), dropped.advance(4)):
        np.testing.assert_array_equal(screened.global_vec,
                                      dropped.global_vec)
        assert rs["time"] == rd["time"]
    assert sum(r["n_screened"] for r in screened.history) > 0
    assert all(r["n_screened"] == 0 for r in dropped.history)


# ---------------------------------------------------------------------------
# NaN storm: unscreened stalls (finite guard), screened converges
# ---------------------------------------------------------------------------

# the shared screening and rollback stages, through the dense client plane
# and a cohort's slot plane (cohort_size < K)
PLANES = pytest.mark.parametrize("plane", [{}, {"cohort_size": K // 2}],
                                 ids=["dense", "cohort"])


@PLANES
def test_nan_storm_unscreened_stalls_screened_progresses(data, plane):
    storm = FaultConfig(nan_frac=0.9, start=1)
    unscr = _fused(data, faults=storm, **plane)
    unscr.advance(1)                      # round 0: faults not yet active
    g1 = np.array(unscr.global_vec, copy=True)
    unscr.advance(4)
    # every active round has >= 1 NaN uploader, the aggregate finite
    # guard holds w_g bit-identically — frozen, never corrupted
    np.testing.assert_array_equal(unscr.global_vec, g1)
    assert np.isfinite(unscr.global_vec).all()

    scr = _fused(data, faults=storm, screen=True, **plane)
    scr.advance(1)
    s1 = np.array(scr.global_vec, copy=True)
    scr.advance(4)
    assert not np.array_equal(scr.global_vec, s1)     # kept converging
    assert np.isfinite(scr.global_vec).all()
    assert sum(r["n_screened"] for r in scr.history) > 0


# ---------------------------------------------------------------------------
# Byzantine: unscreened corrupts ||w_g||, the norm fence contains it
# ---------------------------------------------------------------------------

def test_byzantine_unscreened_corrupts_fence_contains(data):
    """Finite-but-divergent deltas sail past the finite guard: the
    unscreened run is demonstrably corrupted (its trajectory deviates from
    the clean run by an order of magnitude more than the norm-fenced run,
    and ||w_g|| inflates past the clean norm). The instantaneous power cap
    (7) bounds any ONE round's shift — p_k ||x_k||^2 <= P_max attenuates
    huge-norm rows — so corruption shows up as steady trajectory drift,
    not a norm explosion; the screen_max_norm fence removes it at the
    source. Model transmit: the Byzantine rows carry
    w_g + scale (w - w_g), norm ~|scale| ||delta|| >> a clean row's."""
    byz = FaultConfig(byzantine_frac=0.5, byzantine_scale=-50.0, start=1)
    clean = _fused(data, transmit="model")
    clean.advance(6)
    g_clean = np.array(clean.global_vec, copy=True)
    ref = float(np.linalg.norm(g_clean))

    unscr = _fused(data, transmit="model", faults=byz)
    unscr.advance(6)
    dev_unscr = float(np.linalg.norm(unscr.global_vec - g_clean))
    assert np.isfinite(unscr.global_vec).all()
    assert float(np.linalg.norm(unscr.global_vec)) > 1.2 * ref   # inflated
    assert dev_unscr > 0.5 * ref                                 # corrupted

    # clean model-mode payload norms sit at ~||w_g|| (~8 here); the
    # scale=-50 Byzantine rows land at 20-40 — the fence separates them
    fence = _fused(data, transmit="model", faults=byz, screen=True,
                   screen_max_norm=10.0)
    fence.advance(6)
    dev_fence = float(np.linalg.norm(fence.global_vec - g_clean))
    assert dev_fence < 0.15 * dev_unscr
    assert sum(r["n_screened"] for r in fence.history) > 0


@PLANES
def test_rollback_restores_last_good_on_divergence(data, plane):
    """The second line of defense: with screening off, a one-round payload
    blowup (every round-3 local model scaled 100x — past what the power
    cap can attenuate, since EVERY uploader carries it) jumps ||w_g|| by
    orders of magnitude. Unguarded, w_g stays corrupted; with
    divergence_factor the detector fires exactly once, restores the
    last-good global, and the trajectory recovers."""
    def blowup(train):
        def blowup_train(g, x, y, r, *ids):
            tr = train(g, x, y, r, *ids)
            s = jnp.where(jnp.asarray(r) == 3, jnp.float32(100.0),
                          jnp.float32(1.0))
            return jax.tree_util.tree_map(lambda l: l * s, tr)
        return blowup_train

    def make(**kw):
        srv = _fused(data, transmit="model", **plane, **kw)
        base = srv._streams()
        cohort_train = base.cohort_train and blowup(base.cohort_train)
        srv._streams = lambda: base._replace(
            local_train=blowup(base.local_train), cohort_train=cohort_train)
        return srv

    clean = _fused(data, transmit="model", **plane)
    clean.advance(6)
    ref = float(np.linalg.norm(clean.global_vec))

    bare = make()
    bare.advance(6)
    n_bare = float(np.linalg.norm(bare.global_vec))
    assert np.isfinite(n_bare) and n_bare > 5.0 * ref    # stays corrupted

    guard = make(divergence_factor=4.0)
    guard.advance(6)
    rolled = [r["rolled_back"] for r in guard.history]
    assert sum(rolled) == 1.0 and rolled[3] == 1.0
    n_guard = float(np.linalg.norm(guard.global_vec))
    assert np.isfinite(n_guard) and n_guard < 2.0 * ref  # recovered


# ---------------------------------------------------------------------------
# kill-at-round-r + restore == uninterrupted, on every carry layout
# ---------------------------------------------------------------------------

_FAULTS = FaultConfig(nan_frac=0.25, byzantine_frac=0.25, deep_fade_frac=0.2)


def _resume_roundtrip(make, tmp_path, n=4, r=2):
    """full-run vs save-at-r + fresh-driver restore + finish: bit-exact."""
    full = make()
    full.advance(n)
    part = make()
    part.advance(r)
    path = str(tmp_path / "kill.npz")
    part.save_checkpoint(path)
    res = make()                      # fresh driver, never advanced
    assert res.restore_checkpoint(path) == r
    res.advance(n - r)
    np.testing.assert_array_equal(full.global_vec, res.global_vec)
    assert len(res.history) == n
    for rf, rr in zip(full.history, res.history):
        assert rf == rr


def test_resume_bit_exact_fused_dense(data, tmp_path):
    _resume_roundtrip(
        lambda: _fused(data, faults=_FAULTS, screen=True,
                       divergence_factor=4.0), tmp_path)


def test_resume_bit_exact_fused_compressed_cohort(data, tmp_path):
    _resume_roundtrip(
        lambda: _fused(data, faults=_FAULTS, screen=True, cohort_size=4,
                       compress="topk", compress_ratio=0.25,
                       slot_dtype="int8"), tmp_path)


@pytest.mark.multidevice
def test_resume_bit_exact_sharded_dense(data, client_mesh_8, tmp_path):
    _resume_roundtrip(
        lambda: _sharded(data, client_mesh_8, faults=_FAULTS, screen=True),
        tmp_path)


@pytest.mark.multidevice
def test_resume_bit_exact_sharded_grouped_blackout(data, pod_mesh_2x4,
                                                   tmp_path):
    """Grouped carry (held partials) + a pod blackout across the kill
    point: the restored run must replay the blackout window identically."""
    blk = FaultConfig(nan_frac=0.2, pod_blackout=(0,), blackout_start=1,
                      blackout_stop=3)
    _resume_roundtrip(
        lambda: _sharded(data, pod_mesh_2x4, faults=blk, screen=True,
                         group_period=2), tmp_path)


# ---------------------------------------------------------------------------
# compiled structure: screening keeps ONE cross-client all-reduce
# ---------------------------------------------------------------------------

@pytest.mark.multidevice
def test_screened_hlo_single_model_sized_allreduce(data, client_mesh_8):
    """Structural acceptance: with faults + screening enabled the sharded
    round body still compiles to exactly ONE cross-client model-sized
    all-reduce — containment is shard-local masking BEFORE the psum, never
    a second collective. (d = 8070 for the test MLP; the 4097 floor sits
    above the 4096-wide water-filling grid psum and every metric.)"""
    from repro.launch.collectives import axis_crossing_allreduce_count
    srv = _sharded(data, client_mesh_8, faults=_FAULTS, screen=True)
    hlo = srv.compiled_scan_hlo(1)
    shape = tuple(srv.mesh.shape[a] for a in srv.mesh.axis_names)
    assert axis_crossing_allreduce_count(hlo, shape, (0,),
                                         min_elements=4097) == 1
