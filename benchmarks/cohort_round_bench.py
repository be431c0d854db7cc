"""Active-cohort round benchmark: million-client state plane, (m, d)
payload plane.

What the cohort refactor buys is a carry that stops scaling as K x d:
the (K,) scheduler/scenario state plane is O(K) scalars, and model-sized
rows exist only for the m in-flight cohort slots. This module measures
that directly, at three federation scales:

* ``K = 1e3`` — the full driver path (``FusedPAOTA``, real MLP engine,
  d ~= 55k): dense vs ``cohort_size=64`` seconds/round and carry bytes —
  the apples-to-apples driver comparison;
* ``K = 1e3 / 1e5`` — a synthetic runtime-level harness (raw
  ``repro.fl.runtime`` scan with fabricated train/channel/scenario
  streams, d = 16384, m = 256): dense vs cohort where dense still fits,
  cohort alone at 1e5 (the dense carry would be ~6.5 GB — reported
  analytically in ``derived``);
* ``K = 1e6, state-plane-only`` — the acceptance run: the full scenario
  simulator (availability cycle + dropouts), scheduler advance, priority
  top-k slot refill, and AirComp over m = 256 payload rows advance 10
  aggregation periods on the 2-core CPU host. Only (m, d) payloads ever
  materialize; the dense equivalent (64 TB) is physically impossible on
  this box, which is the point.

Every row reports ``carry_bytes`` (actual, summed over the carry's
leaves) and ``dense_carry_bytes`` (what the dense layout would hold at
that K) in ``derived``.

Compressed-payload rows (``_rm16`` = randmask s/d = 1/16, ``_int8`` =
int8 slot storage) measure the next notch: the (m, d) payload plane
shrinks to (m, s) values + (m, s) indices, so at K = 1e6 the carry drops
from the PR 7 ~25.9 MB to the state plane plus a few MB of compressed
slots. The K = 1e6 headline runs error feedback OFF — the parked (K, s)
residual planes are a per-client cost that would reintroduce K-scaling.

``python -m benchmarks.cohort_round_bench smoke`` runs the synthetic
K=1e3 dense/cohort/compressed set only and writes
``BENCH_cohort_round_smoke.json`` (CI fast tier, >2x diff gate); the full
run adds the driver rows and the 1e5/1e6 scales and writes
``BENCH_cohort_round.json`` — committed under experiments/bench/.
"""
from __future__ import annotations

import os
import sys
import time

_SYNTH_D = 16384
_SYNTH_M = 256
_ROUNDS = 10


def _row(name: str, sec: float, setup: float, rounds: int,
         carry_bytes: int, dense_bytes: int) -> dict:
    return {"name": name, "us_per_call": round(sec * 1e6, 1),
            "derived": f"rounds_per_sec={1.0 / sec:.3f};"
                       f"scan_rounds={rounds};setup_s={setup:.2f};"
                       f"carry_bytes={carry_bytes};"
                       f"dense_carry_bytes={dense_bytes}"}


def _carry_bytes(carry) -> int:
    import jax
    return int(sum(l.size * l.dtype.itemsize
                   for l in jax.tree_util.tree_leaves(carry)))


def _dense_bytes(k: int, d: int) -> int:
    """Dense-layout carry footprint at (K, d): the transmit='delta' delta
    plane (K x d f32) + two model copies + the (K,) state plane."""
    return 4 * (k * d + 2 * d) + k * (1 + 4 + 4)


# ---------------------------------------------------------------------------
# synthetic runtime-level harness: the round core with fabricated streams
# ---------------------------------------------------------------------------

def _synth_scan(k: int, m: int, rounds: int = _ROUNDS, *,
                compress: str = "", ratio: float = 1.0,
                slot_dtype: str = "", error_feedback: bool = False):
    """Time the raw ``scan_rounds`` over the cohort (m >= 1) or dense
    (m = 0) carry with synthetic streams: fabricated local updates
    (g + 1e-3 noise rows keyed per round), the real counter latency /
    channel / priority draws, and the full scenario simulator
    (availability cycle + dropouts) over all K clients. ``compress``
    switches the payload plane to the (m, s) compressed form,
    s = round(d * ratio)."""
    import jax
    import jax.numpy as jnp

    from repro.core.aircomp import ChannelConfig, sample_channel_gains
    from repro.core.compress import randmask_indices
    from repro.core.power_control import p2_constants
    from repro.core.scheduler import (TAG_CHANNEL, TAG_COMPRESS, TAG_NOISE,
                                      TAG_QUANT, TAG_SCHED, ScenarioConfig,
                                      counter_latencies, round_tag_key,
                                      scenario_masks)
    from repro.fl.runtime import (RoundCfg, RoundStreams, init_round_carry,
                                  scan_rounds)

    d = _SYNTH_D
    key = jax.random.PRNGKey(0)
    chan = ChannelConfig()
    sc = ScenarioConfig(availability="cycle", avail_period=4,
                        avail_duty=0.5, dropout_prob=0.05)
    c1, c0 = p2_constants(10.0, 0.05, k, d, chan.sigma_n2)
    s = min(d, max(1, round(d * ratio)))
    rcfg = RoundCfg(omega=3.0, c1=c1, c0=c0, p_max_watts=chan.p_max_watts,
                    sigma_n=chan.sigma_n, delta_t=8.0, transmit_delta=True,
                    compress=compress,
                    compress_s=s if compress else 0,
                    slot_dtype=((slot_dtype or "float32") if compress
                                else ""),
                    error_feedback=bool(error_feedback and compress))

    def fan(g, r, ids):
        # tag 12: clear of the scheduler's reserved draw tags (0-9)
        n = jax.random.normal(round_tag_key(key, r, 12),
                              (ids.shape[0], d), jnp.float32)
        return g[None, :] + jnp.float32(1e-3) * n

    compress_mask = quant_key = None
    if compress == "randmask" and s < d:
        compress_mask = lambda r: randmask_indices(
            round_tag_key(key, r, TAG_COMPRESS), d, s)
    if rcfg.slot_dtype == "int8":
        quant_key = lambda r: round_tag_key(key, r, TAG_QUANT)
    streams = RoundStreams(
        local_train=lambda g, x, y, r: fan(g, r, jnp.arange(k)),
        latencies=lambda r: counter_latencies(key, r, k, 5.0, 15.0),
        channel=lambda t: sample_channel_gains(
            round_tag_key(key, t, TAG_CHANNEL), k, chan),
        noise_key=lambda t: round_tag_key(key, t, TAG_NOISE),
        scenario=lambda t: scenario_masks(key, t, k, sc),
        cohort_train=lambda g, x, y, r, ids: fan(g, r, ids),
        sched_priority=lambda r: jax.random.uniform(
            round_tag_key(key, r, TAG_SCHED), (k,)),
        compress_mask=compress_mask,
        quant_key=quant_key,
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
    return sec, setup, nbytes


def _synth_rows(ks_cohort, with_dense_1e3: bool) -> list:
    rows = []
    if with_dense_1e3:
        sec, setup, nb = _synth_scan(1000, 0)
        rows.append(_row("cohort_round/synth_dense_k1000", sec, setup,
                         _ROUNDS, nb, _dense_bytes(1000, _SYNTH_D)))
    for k in ks_cohort:
        sec, setup, nb = _synth_scan(k, _SYNTH_M)
        rows.append(_row(f"cohort_round/synth_cohort_m{_SYNTH_M}_k{k}",
                         sec, setup, _ROUNDS, nb,
                         _dense_bytes(k, _SYNTH_D)))
    return rows


def _synth_compressed_rows(ks, *, slot_dtype: str = "",
                           error_feedback: bool = False) -> list:
    """randmask s/d = 1/16 compressed-cohort rows. EF defaults OFF: the
    parked (K, s) residual planes scale per-client, which is exactly what
    the K = 1e6 headline must not pay."""
    rows = []
    sfx = "_rm16" + (f"_{slot_dtype}" if slot_dtype else "")
    if error_feedback:
        sfx += "_ef"
    for k in ks:
        sec, setup, nb = _synth_scan(k, _SYNTH_M, compress="randmask",
                                     ratio=1.0 / 16.0,
                                     slot_dtype=slot_dtype,
                                     error_feedback=error_feedback)
        rows.append(_row(f"cohort_round/synth_cohort_m{_SYNTH_M}_k{k}{sfx}",
                         sec, setup, _ROUNDS, nb,
                         _dense_bytes(k, _SYNTH_D)))
    return rows


# ---------------------------------------------------------------------------
# driver-level rows: the real FusedPAOTA path at K = 1e3
# ---------------------------------------------------------------------------

def _driver_rows(k: int = 1000, m: int = 64) -> list:
    import jax
    import numpy as np

    from repro.core import ChannelConfig, SchedulerConfig
    from repro.data.partition import partition_noniid
    from repro.data.pipeline import build_federation
    from repro.data.synthetic import make_mnist_like
    from repro.fl import BatchedEngine, FusedPAOTA, PAOTAConfig
    from repro.models.mlp import init_mlp_params, mlp_loss

    x, y, _, _ = make_mnist_like(n_train=20000, n_test=10, seed=1234)
    parts = partition_noniid(y, n_clients=k, sizes=(16, 24), seed=0)

    def srv(cohort, **kw):
        fed = build_federation(x, y, parts, seed=0)
        eng = BatchedEngine(fed, mlp_loss, batch_size=1, lr=0.1,
                            local_steps=1)
        return FusedPAOTA(init_mlp_params(jax.random.PRNGKey(0)), eng,
                          ChannelConfig(), SchedulerConfig(n_clients=k,
                                                           seed=0),
                          PAOTAConfig(transmit="delta"),
                          cohort_size=cohort, **kw)

    rows = []
    configs = (("dense", None, {}), (f"cohort_m{m}", m, {}),
               # compressed driver row: randmask 1/16 with error feedback
               # (the driver scale can afford the (K, s) parked planes)
               (f"cohort_m{m}_rm16", m,
                {"compress": "randmask", "compress_ratio": 1.0 / 16.0}))
    for label, cohort, kw in configs:
        t0 = time.perf_counter()
        s = srv(cohort, **kw)
        s.advance(_ROUNDS)
        setup = time.perf_counter() - t0
        nb = _carry_bytes(s._carry)
        t0 = time.perf_counter()
        s.advance(_ROUNDS)
        sec = (time.perf_counter() - t0) / _ROUNDS
        assert np.isfinite(s.global_vec).all()
        rows.append(_row(f"cohort_round/fused_{label}_mlp_k{k}", sec, setup,
                         _ROUNDS, nb, _dense_bytes(k, s.d)))
    return rows


def run(smoke: bool = False) -> list:
    rows = _synth_rows((1000,), with_dense_1e3=True)
    # compressed smoke pair: f32 slots with EF on (the accuracy-preserving
    # config), int8 slots EF off (the smallest carry)
    rows += _synth_compressed_rows((1000,), error_feedback=True)
    rows += _synth_compressed_rows((1000,), slot_dtype="int8")
    if smoke:
        return rows
    rows += _driver_rows()
    # the acceptance scales: K = 1e5, then the million-client state plane
    # advancing 10 periods with only (m, d) payload rows materialized
    rows += _synth_rows((100_000, 1_000_000), with_dense_1e3=False)
    # the compressed headline: K = 1e6 at s/d = 1/16 (EF off — parked
    # residuals would reintroduce per-client payload scaling)
    rows += _synth_compressed_rows((1_000_000,))
    rows += _synth_compressed_rows((1_000_000,), slot_dtype="int8")
    return rows


def main():
    smoke = "smoke" in sys.argv[1:]
    rows = run(smoke=smoke)
    print("name,us_per_call,derived")
    for row in rows:
        print(f"{row['name']},{row['us_per_call']},{row['derived']}",
              flush=True)
    from benchmarks.common import write_bench_artifact
    name = "cohort_round_smoke" if smoke else "cohort_round"
    path = write_bench_artifact(
        name, rows, extra={"synth_d": _SYNTH_D, "synth_m": _SYNTH_M,
                           "rounds": _ROUNDS, "smoke": smoke})
    print(f"# artifact -> {path}", flush=True)


if __name__ == "__main__":
    main()
