"""Slot-clock precision: the host scheduler and the fused scan must decide
"training finished by this aggregation slot" IDENTICALLY at any horizon.

The old fused formulation accumulated an absolute f32 clock and compared
``busy_until <= time``; at delta_t values inexact in binary (0.1) the f32
products drift from the host's f64 clock by a growing ulp and eventually
flip a slot boundary — silently forking the two trajectories mid-run. The
fix carries the raw latency DRAW and evaluates the exact relative
predicate ``lat <= (round + 1 - model_round) * delta_t`` (one IEEE
rounding in the draw's own dtype) on both sides — ``repro.core.scheduler
.slot_ready`` — so the masks are bit-identical, not approximately close.

The regression here runs delta_t = 0.1 for >= 1000 rounds with draws
tight around small slot multiples (the regime where absolute-clock
rounding reliably flips boundaries) and pins the host counter-mode
scheduler against the pure-jnp scan transition bit for bit.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import SchedulerConfig, SemiAsyncScheduler
from repro.core.scheduler import (counter_latencies, sched_advance,
                                  sched_broadcast, slot_ready)

K, R, DELTA_T = 64, 2000, 0.1
LAT_LO, LAT_HI = 0.15, 0.35      # finishes land 2-4 slots out — every
                                 # draw sits near a small slot boundary


def _device_masks(seed):
    """The fused-round scheduler transition alone (sched_advance +
    sched_broadcast in a lax.scan over counter draws) — exactly what
    ``paota_round_step`` runs in its schedule stages on a dense carry (a
    cohort carry adds slot refill and idling between the two)."""
    key = jax.random.PRNGKey(seed)

    def step(c, t):
        ready, busy_lat, model_round = c
        rdy, stal = sched_advance(ready, busy_lat, model_round, t, DELTA_T)
        lat = counter_latencies(key, t + 1, K, LAT_LO, LAT_HI)
        nxt = sched_broadcast(rdy, busy_lat, model_round, rdy, lat, t + 1)
        return nxt, (rdy, stal)

    init = (jnp.zeros((K,), bool),
            counter_latencies(key, 0, K, LAT_LO, LAT_HI),
            jnp.zeros((K,), jnp.int32))
    _, (ready, stal) = jax.lax.scan(step, init, jnp.arange(R))
    return np.asarray(ready), np.asarray(stal)


def _host_masks(seed):
    """The host reference: SemiAsyncScheduler in counter mode (f32 draws,
    f64 host arithmetic everywhere else)."""
    sched = SemiAsyncScheduler(SchedulerConfig(
        n_clients=K, delta_t=DELTA_T, lat_lo=LAT_LO, lat_hi=LAT_HI,
        seed=seed, rng="counter"))
    sched.start_round(range(K))
    ready = np.zeros((R, K), bool)
    stal = np.zeros((R, K), np.int64)
    for r in range(R):
        uploaders, s = sched.advance_to_aggregation()
        ready[r, uploaders] = True
        stal[r] = s
        sched.start_round(uploaders)
    return ready, stal


def test_host_and_fused_masks_bit_identical_long_horizon():
    dev_ready, dev_stal = _device_masks(seed=0)
    host_ready, host_stal = _host_masks(seed=0)
    # every client participates and goes back busy many times — the masks
    # are exercised, not vacuously all-True/all-False
    flips = np.sum(dev_ready[1:] != dev_ready[:-1])
    assert flips > R                # thousands of boundary decisions
    np.testing.assert_array_equal(dev_ready, host_ready)
    np.testing.assert_array_equal(dev_stal.astype(np.int64), host_stal)


def test_absolute_f32_clock_would_flip_boundaries():
    """The failure mode the relative predicate removes, reconstructed as
    the OLD formulation computed it: the fused carry stored
    ``busy_until = f32(broadcast_time) + f32(lat)`` and compared it to the
    f32 slot clock, while the host compared the same quantities in f64.
    Over delta_t = 0.1 horizons the two absolute forms disagree on real
    draws — which is exactly why the carry now stores the raw draw and
    both sides evaluate ``slot_ready`` (documents the bug; fails if this
    regression scenario ever goes stale)."""
    key = jax.random.PRNGKey(0)
    disagree = 0
    for r in range(R):                  # broadcast rounds across the horizon
        lat = np.asarray(counter_latencies(key, r, K, LAT_LO, LAT_HI))
        busy32 = np.float32(r) * np.float32(DELTA_T) + lat  # old fused carry
        busy64 = r * float(DELTA_T) + lat.astype(np.float64)  # host clock
        for m in range(1, 5):
            slot32 = np.float32(r + m) * np.float32(DELTA_T)
            slot64 = (r + m) * float(DELTA_T)
            disagree += int(np.sum((busy32 <= slot32) != (busy64 <= slot64)))
        # the NEW predicate agrees with itself by construction on the same
        # draws: one rounding, same dtype on both sides
        mr = np.zeros(K, np.int64) + r
        for m in range(1, 5):
            host = slot_ready(lat, mr, r + m - 1, DELTA_T)
            dev = np.asarray(slot_ready(jnp.asarray(lat),
                                        jnp.asarray(mr, jnp.int32),
                                        jnp.int32(r + m - 1), DELTA_T))
            np.testing.assert_array_equal(host, dev)
    assert disagree > 0


def test_slot_ready_matches_between_numpy_and_jnp():
    """The predicate itself is one shared function evaluated over numpy on
    the host and jnp on device — same dtype, same ops, same bits."""
    rng = np.random.default_rng(3)
    lat = rng.uniform(LAT_LO, LAT_HI, 256).astype(np.float32)
    model_round = rng.integers(0, 1000, 256)
    for round_idx in (0, 7, 999, 10_000, 100_000):
        host = slot_ready(lat, model_round, round_idx, DELTA_T)
        dev = np.asarray(slot_ready(jnp.asarray(lat),
                                    jnp.asarray(model_round, jnp.int32),
                                    jnp.int32(round_idx), DELTA_T))
        valid = model_round <= round_idx + 1
        np.testing.assert_array_equal(host[valid], dev[valid])
