"""Canonical tracked round-perf series: the PAOTA delta plane.

This is the cross-PR perf trajectory for the aggregation period itself —
the (K, d) data-plane arithmetic (eq.-25 stats, water-filled powers,
AirComp superposition, carry update) that the round-stats / superpose
kernels target. The model is sized so that plane dominates: an MLP with
``REPRO_BENCH_HIDDEN`` (default 64) hidden units gives d ~= 55k, and local
training is held to ONE local SGD step on batch 1, so per-round cost is
memory traffic over the stacked (K, d) carry, not SGD compute.

Per K in {16, 1000} (smoke: K=16 only):

* ``round_perf/host_raveled_k{K}``    — host reference seconds/round
  (``PAOTAServer``, counter RNG + waterfill_jnp: the same math as the
  on-device drivers, host-Python staging).
* ``round_perf/fused_raveled_k{K}``   — ``FusedPAOTA`` seconds/round,
  steady-state, amortized over one R-round ``lax.scan`` device call
  (paper-default transmit='model': clients superpose full local models).
* ``round_perf/fused_pytree_k{K}``    — same, params carried as a pytree.
* ``round_perf/fused_{raveled,pytree}_delta_k{K}`` — transmit='delta':
  the carry IS the delta plane (no pending stack), the purest view of
  the one-pass delta-plane arithmetic this series tracks.
* ``round_perf/sharded_raveled_k{K}`` / ``round_perf/sharded_pytree_k{K}``
  — ``ShardedPAOTA`` over the forced 8-virtual-device CPU mesh
  (subprocess, same pattern as benchmarks/sharded_round_bench; virtual
  devices share the physical cores, so these track orchestration cost).

``python -m benchmarks.round_perf_bench smoke`` runs the K=16 subset and
writes ``BENCH_round_perf_smoke.json`` (the CI fast-tier guard wired into
scripts/ci.sh with the >2x diff gate); the full run writes
``BENCH_round_perf.json`` — committed under experiments/bench/ as the
tracked baseline the next PR diffs against.
"""
from __future__ import annotations

import json
import os
import sys
import time

_ROUNDS = {16: 20, 1000: 5}          # scan length R per federation size
_BATCH, _STEPS, _SIZES = 1, 1, (16, 24)


def _hidden() -> int:
    return int(os.environ.get("REPRO_BENCH_HIDDEN", "64"))


def _make_engine(k: int, seed: int = 0):
    from repro.data.partition import partition_noniid
    from repro.data.pipeline import build_federation
    from repro.data.synthetic import make_mnist_like
    from repro.fl import BatchedEngine
    from repro.models.mlp import mlp_loss
    x, y, _, _ = make_mnist_like(n_train=min(max(20 * k, 2000), 20000),
                                 n_test=10, seed=1234)
    parts = partition_noniid(y, n_clients=k, sizes=_SIZES, seed=seed)
    fed = build_federation(x, y, parts, seed=seed)
    return BatchedEngine(fed, mlp_loss, batch_size=_BATCH, lr=0.1,
                         local_steps=_STEPS)


def _params(seed: int = 0):
    import jax
    from repro.models.mlp import init_mlp_params
    return init_mlp_params(jax.random.PRNGKey(seed), hidden=_hidden())


def _row(name: str, sec: float, setup: float, rounds: int) -> dict:
    return {"name": name, "us_per_call": round(sec * 1e6, 1),
            "derived": f"rounds_per_sec={1.0 / sec:.3f};"
                       f"scan_rounds={rounds};setup_s={setup:.2f}"}


def _time_host(k: int, seed: int = 0):
    from repro.core import ChannelConfig, SchedulerConfig
    from repro.fl import PAOTAConfig, PAOTAServer
    rounds = _ROUNDS[k]
    t0 = time.perf_counter()
    srv = PAOTAServer(_params(seed), _make_engine(k, seed), ChannelConfig(),
                      SchedulerConfig(n_clients=k, seed=seed, rng="counter"),
                      PAOTAConfig(rng="counter", solver="waterfill_jnp",
                                  seed=seed))
    srv.round()
    setup = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(rounds):
        srv.round()
    return _row(f"round_perf/host_raveled_k{k}",
                (time.perf_counter() - t0) / rounds, setup, rounds)


def _time_driver(cls, k: int, params_mode: str, seed: int = 0,
                 transmit: str = "model", **kw):
    import numpy as np
    from repro.core import ChannelConfig, SchedulerConfig
    from repro.fl import PAOTAConfig
    rounds = _ROUNDS[k]
    t0 = time.perf_counter()
    srv = cls(_params(seed), _make_engine(k, seed), ChannelConfig(),
              SchedulerConfig(n_clients=k, seed=seed),
              PAOTAConfig(seed=seed, transmit=transmit),
              params_mode=params_mode, **kw)
    srv.advance(rounds)                 # compile + init
    setup = time.perf_counter() - t0
    t0 = time.perf_counter()
    srv.advance(rounds)                 # steady state: one scan device call
    sec = (time.perf_counter() - t0) / rounds
    assert np.isfinite(srv.global_vec).all()
    return sec, setup, rounds


def _measure_local(ks) -> list:
    """Host + fused rows on the ambient (single-device) backend."""
    from repro.fl import FusedPAOTA
    rows = []
    for k in ks:
        rows.append(_time_host(k))
        for mode in ("raveled", "pytree"):
            sec, setup, rounds = _time_driver(FusedPAOTA, k, mode)
            rows.append(_row(f"round_perf/fused_{mode}_k{k}", sec, setup,
                             rounds))
            sec, setup, rounds = _time_driver(FusedPAOTA, k, mode,
                                              transmit="delta")
            rows.append(_row(f"round_perf/fused_{mode}_delta_k{k}", sec,
                             setup, rounds))
    return rows


def _measure_sharded(ks) -> list:
    """Sharded rows — runs INSIDE the forced-device subprocess."""
    import jax
    from repro.fl import ShardedPAOTA
    from repro.launch.mesh import make_client_mesh
    mesh = make_client_mesh(min(len(jax.devices()), 8))
    rows = []
    for k in ks:
        for mode in ("raveled", "pytree"):
            sec, setup, rounds = _time_driver(ShardedPAOTA, k, mode,
                                              mesh=mesh)
            rows.append(_row(f"round_perf/sharded_{mode}_k{k}", sec, setup,
                             rounds))
    return rows


def run(ks=(16, 1000)) -> list:
    from benchmarks.common import run_multidevice
    rows = _measure_local(ks)
    rows += run_multidevice("benchmarks.round_perf_bench", 8,
                            [str(k) for k in ks],
                            lambda: _measure_sharded(ks))
    return rows


def main():
    args = sys.argv[1:]
    if "--emit" in args:                     # forced-device child
        i = args.index("--emit")
        out_path, ks = args[i + 1], tuple(int(k) for k in args[i + 2:])
        rows = _measure_sharded(ks)
        with open(out_path, "w") as f:
            json.dump(rows, f)
        return
    smoke = "smoke" in args
    ks = (16,) if smoke else (16, 1000)
    rows = run(ks=ks)
    print("name,us_per_call,derived")
    for row in rows:
        print(f"{row['name']},{row['us_per_call']},{row['derived']}",
              flush=True)
    from benchmarks.common import write_bench_artifact
    name = "round_perf_smoke" if smoke else "round_perf"
    path = write_bench_artifact(name, rows,
                                extra={"ks": list(ks), "hidden": _hidden(),
                                       "batch": _BATCH, "local_steps": _STEPS,
                                       "forced_devices_sharded": 8})
    print(f"# artifact -> {path}", flush=True)


if __name__ == "__main__":
    main()
