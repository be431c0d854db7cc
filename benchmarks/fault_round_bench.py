"""Fault-tolerant round benchmark: what containment and checkpointing cost.

The fault-tolerance planes (ISSUE 10) are all trace-time opt-ins; this
module prices each one against the unguarded round:

* ``K = 1e3`` synthetic runtime-level rows (raw ``repro.fl.runtime`` scan,
  d = 16384, fabricated train/channel streams): the unguarded dense round
  vs ``screen=True`` on clean traffic (pure screening overhead — the ok
  mask rides the existing stats sweep, so this should be noise) vs a
  faulty run (NaN + Byzantine + deep-fade injection through the real
  ``FaultConfig`` helpers) under screening;
* ``K = 1e3`` driver rows (real ``FusedPAOTA``, MLP engine): baseline vs
  the full fault-tolerance stack (faults + screening + divergence
  rollback) vs ``checkpoint_every=5`` (two full-carry snapshots inside
  the timed 10-round window — the serialization + atomic-rename cost);
* ``K = 1e6`` cohort rows (m = 256 slots, the PR-8 state-plane scale):
  the fault stack at the scale where the (K,) fault masks are the only
  per-client cost — screening stays on the (m, d) payload plane.

Every screened row reports ``screened_per_round`` in ``derived`` so the
series also tracks that injection actually engages the screen.

``python -m benchmarks.fault_round_bench smoke`` runs the synthetic
K=1e3 trio only and writes ``BENCH_fault_round_smoke.json`` (CI fast
tier, >2x diff gate); the full run adds the driver and K=1e6 rows and
writes ``BENCH_fault_round.json``.
"""
from __future__ import annotations

import os
import sys
import tempfile
import time

_SYNTH_D = 16384
_SYNTH_M = 256
_ROUNDS = 10

# the injected storm for every faulty row: 5% NaN + 5% Byzantine uploads,
# 5% deep-fade channel outliers, live from round 1
_STORM = dict(nan_frac=0.05, byzantine_frac=0.05, deep_fade_frac=0.05,
              start=1)


def _row(name: str, sec: float, setup: float, rounds: int,
         carry_bytes: int, screened: float) -> dict:
    return {"name": name, "us_per_call": round(sec * 1e6, 1),
            "derived": f"rounds_per_sec={1.0 / sec:.3f};"
                       f"scan_rounds={rounds};setup_s={setup:.2f};"
                       f"carry_bytes={carry_bytes};"
                       f"screened_per_round={screened:.2f}"}


def _carry_bytes(carry) -> int:
    import jax
    return int(sum(l.size * l.dtype.itemsize
                   for l in jax.tree_util.tree_leaves(carry)))


# ---------------------------------------------------------------------------
# synthetic runtime-level harness: the round core with fabricated streams
# ---------------------------------------------------------------------------

def _synth_scan(k: int, m: int, rounds: int = _ROUNDS, *,
                faults=None, screen: bool = False):
    """Time the raw ``scan_rounds`` over the dense (m = 0) or cohort
    carry with synthetic streams; ``faults`` (a ``FaultConfig``) corrupts
    the fabricated local updates and channel draws through the same
    helpers the drivers use, ``screen`` arms per-row containment."""
    import jax
    import jax.numpy as jnp

    from repro.core.aircomp import ChannelConfig, sample_channel_gains
    from repro.core.power_control import p2_constants
    from repro.core.scheduler import (TAG_CHANNEL, TAG_NOISE, TAG_SCHED,
                                      ScenarioConfig, counter_latencies,
                                      fault_channel_mask,
                                      fault_payload_masks,
                                      inject_payload_faults, round_tag_key,
                                      scenario_masks)
    from repro.fl.runtime import (RoundCfg, RoundStreams, init_round_carry,
                                  scan_rounds)

    d = _SYNTH_D
    key = jax.random.PRNGKey(0)
    chan = ChannelConfig()
    sc = ScenarioConfig(availability="cycle", avail_period=4,
                        avail_duty=0.5, dropout_prob=0.05)
    c1, c0 = p2_constants(10.0, 0.05, k, d, chan.sigma_n2)
    rcfg = RoundCfg(omega=3.0, c1=c1, c0=c0, p_max_watts=chan.p_max_watts,
                    sigma_n=chan.sigma_n, delta_t=8.0, transmit_delta=True,
                    screen=bool(screen))

    def fan(g, r, ids):
        # tag 12: clear of the scheduler's reserved draw tags (0-10)
        n = jax.random.normal(round_tag_key(key, r, 12),
                              (ids.shape[0], d), jnp.float32)
        rows = g[None, :] + jnp.float32(1e-3) * n
        if faults is not None and faults.has_payload_faults:
            nm, bm = fault_payload_masks(key, r, k, faults)
            rows = inject_payload_faults(rows, g, nm[ids], bm[ids], faults)
        return rows

    def channel(t):
        h = sample_channel_gains(round_tag_key(key, t, TAG_CHANNEL), k, chan)
        if faults is not None and faults.has_channel_faults:
            fade = fault_channel_mask(key, t, k, faults)
            h = jnp.where(fade, h * jnp.float32(faults.deep_fade_gain), h)
        return h

    streams = RoundStreams(
        local_train=lambda g, x, y, r: fan(g, r, jnp.arange(k)),
        latencies=lambda r: counter_latencies(key, r, k, 5.0, 15.0),
        channel=channel,
        noise_key=lambda t: round_tag_key(key, t, TAG_NOISE),
        scenario=lambda t: scenario_masks(key, t, k, sc),
        cohort_train=lambda g, x, y, r, ids: fan(g, r, ids),
        sched_priority=lambda r: jax.random.uniform(
            round_tag_key(key, r, TAG_SCHED), (k,)),
    )
    g0 = jnp.zeros((d,), jnp.float32)
    x = y = jnp.zeros((1,), jnp.float32)

    t0 = time.perf_counter()
    carry = jax.jit(lambda v: init_round_carry(
        v, x, y, streams=streams, pending_dtype="float32",
        keep_pending=False, rcfg=rcfg, m=m))(g0)
    nbytes = _carry_bytes(carry)
    scan = jax.jit(lambda c: scan_rounds(c, x, y, rounds, rcfg=rcfg,
                                         streams=streams),
                   donate_argnums=(0,))
    carry, outs = jax.block_until_ready(scan(carry))    # compile + run
    setup = time.perf_counter() - t0
    t0 = time.perf_counter()
    carry, outs = jax.block_until_ready(scan(carry))    # steady state
    sec = (time.perf_counter() - t0) / rounds
    import numpy as np
    assert np.isfinite(np.asarray(carry.global_vec)).all()
    screened = float(np.asarray(outs["n_screened"]).sum()) / rounds
    return sec, setup, nbytes, screened


def _synth_rows(k: int, m: int = 0) -> list:
    from repro.core.scheduler import FaultConfig
    sfx = f"_m{m}" if m else "_dense"
    rows = []
    for label, kw in (
            ("baseline", {}),
            ("screen", dict(screen=True)),
            ("faulty_screened", dict(faults=FaultConfig(**_STORM),
                                     screen=True))):
        sec, setup, nb, scr = _synth_scan(k, m, **kw)
        rows.append(_row(f"fault_round/synth_{label}{sfx}_k{k}", sec,
                         setup, _ROUNDS, nb, scr))
    return rows


# ---------------------------------------------------------------------------
# driver-level rows: the real FusedPAOTA path at K = 1e3
# ---------------------------------------------------------------------------

def _driver_rows(k: int = 1000) -> list:
    import jax
    import numpy as np

    from repro.core import ChannelConfig, SchedulerConfig
    from repro.core.scheduler import FaultConfig
    from repro.data.partition import partition_noniid
    from repro.data.pipeline import build_federation
    from repro.data.synthetic import make_mnist_like
    from repro.fl import BatchedEngine, FusedPAOTA, PAOTAConfig
    from repro.models.mlp import init_mlp_params, mlp_loss

    x, y, _, _ = make_mnist_like(n_train=20000, n_test=10, seed=1234)
    parts = partition_noniid(y, n_clients=k, sizes=(16, 24), seed=0)

    def srv(**kw):
        fed = build_federation(x, y, parts, seed=0)
        eng = BatchedEngine(fed, mlp_loss, batch_size=1, lr=0.1,
                            local_steps=1)
        return FusedPAOTA(init_mlp_params(jax.random.PRNGKey(0)), eng,
                          ChannelConfig(), SchedulerConfig(n_clients=k,
                                                           seed=0),
                          PAOTAConfig(transmit="delta"), **kw)

    rows = []
    with tempfile.TemporaryDirectory() as ckpt_dir:
        configs = (
            ("baseline", {}),
            ("fault_tol", dict(faults=FaultConfig(**_STORM), screen=True,
                               divergence_factor=4.0)),
            # snapshot cost: 2 full-carry checkpoints land inside the
            # timed 10-round window (serialize + fsync-free atomic rename)
            ("ckpt5", dict(checkpoint_every=5, checkpoint_dir=ckpt_dir)),
        )
        for label, kw in configs:
            t0 = time.perf_counter()
            s = srv(**kw)
            s.advance(_ROUNDS)
            setup = time.perf_counter() - t0
            nb = _carry_bytes(s._carry)
            t0 = time.perf_counter()
            s.advance(_ROUNDS)
            sec = (time.perf_counter() - t0) / _ROUNDS
            assert np.isfinite(s.global_vec).all()
            scr = sum(r["n_screened"] for r in s.history) / len(s.history)
            rows.append(_row(f"fault_round/fused_{label}_mlp_k{k}", sec,
                             setup, _ROUNDS, nb, scr))
        n_ckpt = len(os.listdir(ckpt_dir))
        assert n_ckpt >= 2, f"checkpoint_every=5 wrote {n_ckpt} snapshots"
    return rows


def run(smoke: bool = False) -> list:
    rows = _synth_rows(1000)
    if smoke:
        return rows
    rows += _driver_rows()
    # the acceptance scale: the fault stack on the million-client cohort
    # state plane — (K,) fault masks are the only per-client cost
    rows += _synth_rows(1_000_000, m=_SYNTH_M)
    return rows


def main():
    smoke = "smoke" in sys.argv[1:]
    rows = run(smoke=smoke)
    print("name,us_per_call,derived")
    for row in rows:
        print(f"{row['name']},{row['us_per_call']},{row['derived']}",
              flush=True)
    from benchmarks.common import write_bench_artifact
    name = "fault_round_smoke" if smoke else "fault_round"
    path = write_bench_artifact(
        name, rows, extra={"synth_d": _SYNTH_D, "synth_m": _SYNTH_M,
                           "rounds": _ROUNDS, "storm": _STORM,
                           "smoke": smoke})
    print(f"# artifact -> {path}", flush=True)


if __name__ == "__main__":
    main()
