"""Mesh-sharded PAOTA round vs the single-device fused scan.

Strong scaling: same K, 1 device (``FusedPAOTA``) vs the 8-virtual-device
CPU mesh (``ShardedPAOTA`` — per-client stages parallel, AirComp/P2 as
psums). Weak scaling: the sharded K on 8 devices against the fused K/8 on
one device (per-device client load held constant; 1.0x = perfect).

Per K in {1000, 10000} (smoke: K=16):

* ``sharded_round/fused_k{K}``        — fused seconds/round, 1 device.
* ``sharded_round/sharded_k{K}_dev8`` — sharded seconds/round, 8 devices.
* ``sharded_round/strong_k{K}``       — fused / sharded at equal K.
* ``sharded_round/weak_k{K}``         — fused@K/8 / sharded@K.

Virtual CPU devices share the same 2 physical cores, so these numbers
measure the collective/orchestration overhead of the sharded program, not
real speedup — the strong ratio is the lower bound a real 8-chip mesh
starts from (see EXPERIMENTS.md §Sharded PAOTA round).

Timing protocol: the headline ``sharded_k{K}`` / ``fused_k{K}`` rows are
AMORTIZED — R rounds advance as one chunked ``lax.scan`` dispatch (the
way any real training loop drives these servers), divided by R. At smoke
scale (K=16) the per-dispatch shard_map overhead on 8 virtual devices is
~100x the per-round math, so a tiny R made the old artifact read as a
600 ms/round "regression" that was really ~24 ms of round work plus
dispatch; the smoke now scans R=24 and ALSO reports the single-round
dispatch cost as an explicit ``..._dispatch`` row so both numbers stay
tracked instead of blended.

On the CPU, host-device forcing must happen before jax initializes, so
``run()`` re-execs this module in a subprocess with ``XLA_FLAGS=--xla_
force_host_platform_device_count=8`` and parses the rows back — callable
from ``benchmarks.run`` no matter what the parent process already
imported. On an accelerator it measures in-process over ``jax.devices()``
(``common.run_multidevice``).

``python -m benchmarks.sharded_round_bench smoke`` runs the K=16 pairing
(the CI guard that keeps the shard_map path compiling) and writes the
``BENCH_sharded_round_smoke.json`` artifact.
"""
from __future__ import annotations

import json
import sys
import time

_SETTINGS = {  # K -> (size ladder, batch, local steps, scan rounds)
    16: ((48, 64), 32, 5, 24),       # smoke: R large enough to amortize
    125: ((48, 64), 32, 5, 10),      # weak-scaling reference for K=1000
    1000: ((48, 64), 32, 5, 10),
    1250: ((16, 24), 16, 2, 3),      # weak-scaling reference for K=10000
    10000: ((16, 24), 16, 2, 3),
}


def _make_engine(k: int, seed: int = 0):
    from repro.data.partition import partition_noniid
    from repro.data.pipeline import build_federation
    from repro.data.synthetic import make_mnist_like
    from repro.fl import BatchedEngine
    from repro.models.mlp import mlp_loss
    sizes, batch, steps, _ = _SETTINGS[k]
    x, y, _, _ = make_mnist_like(n_train=min(max(20 * k, 2000), 20000),
                                 n_test=10, seed=1234)
    parts = partition_noniid(y, n_clients=k, sizes=sizes, seed=seed)
    fed = build_federation(x, y, parts, seed=seed)
    return BatchedEngine(fed, mlp_loss, batch_size=batch, lr=0.1,
                         local_steps=steps)


def _time_server(cls, k: int, seed: int = 0, measure_dispatch: bool = False,
                 **kw):
    """(amortized seconds/round, setup seconds, per-dispatch seconds or
    None). Amortized = one chunked R-round ``advance`` scan / R (the way a
    training loop drives the server); per-dispatch = a single-round
    ``advance(1)`` call, which at smoke scale is dominated by shard_map
    dispatch, not round math. Setup = construction + first advance
    (compile + init federation train)."""
    import jax
    import numpy as np
    from repro.core import ChannelConfig, SchedulerConfig
    from repro.fl import PAOTAConfig
    from repro.models.mlp import init_mlp_params
    rounds = _SETTINGS[k][3]
    params = init_mlp_params(jax.random.PRNGKey(seed))
    t0 = time.perf_counter()
    srv = cls(params, _make_engine(k, seed), ChannelConfig(),
              SchedulerConfig(n_clients=k, seed=seed),
              PAOTAConfig(seed=seed), **kw)
    srv.advance(rounds)
    setup = time.perf_counter() - t0
    t0 = time.perf_counter()
    srv.advance(rounds)
    sec = (time.perf_counter() - t0) / rounds
    dispatch = None
    if measure_dispatch:
        srv.advance(1)                    # compile the length-1 scan
        t0 = time.perf_counter()
        for _ in range(3):
            srv.advance(1)
        dispatch = (time.perf_counter() - t0) / 3
    assert np.isfinite(srv.global_vec).all()
    return sec, setup, dispatch


def _measure(ks, dispatch_rows: bool = False) -> list:
    """Runs INSIDE the forced-device subprocess. ``dispatch_rows`` (the
    smoke) also emits per-dispatch single-round rows next to the
    amortized chunked-scan headline."""
    import jax
    from repro.fl import FusedPAOTA, ShardedPAOTA
    from repro.launch.mesh import make_client_mesh
    n_dev = len(jax.devices())
    mesh = make_client_mesh(min(n_dev, 8))
    rows = []
    for k in ks:
        rounds = _SETTINGS[k][3]
        fused_s, fused_setup, fused_disp = _time_server(
            FusedPAOTA, k, measure_dispatch=dispatch_rows)
        rows.append({"name": f"sharded_round/fused_k{k}",
                     "us_per_call": round(fused_s * 1e6, 1),
                     "derived": f"rounds_per_sec={1.0 / fused_s:.3f};"
                                f"scan_rounds={rounds};"
                                f"setup_s={fused_setup:.2f}"})
        shard_s, shard_setup, shard_disp = _time_server(
            ShardedPAOTA, k, mesh=mesh, measure_dispatch=dispatch_rows)
        rows.append({"name": f"sharded_round/sharded_k{k}_dev{mesh.size}",
                     "us_per_call": round(shard_s * 1e6, 1),
                     "derived": f"rounds_per_sec={1.0 / shard_s:.3f};"
                                f"scan_rounds={rounds};"
                                f"setup_s={shard_setup:.2f}"})
        if dispatch_rows:
            rows.append({"name": f"sharded_round/fused_k{k}_dispatch",
                         "us_per_call": round(fused_disp * 1e6, 1),
                         "derived": "single_round_advance=1_dispatch"})
            rows.append(
                {"name": f"sharded_round/sharded_k{k}_dev{mesh.size}"
                         f"_dispatch",
                 "us_per_call": round(shard_disp * 1e6, 1),
                 "derived": f"single_round_advance=1_dispatch;"
                            f"overhead_vs_amortized="
                            f"{shard_disp / shard_s:.1f}x"})
        rows.append({"name": f"sharded_round/strong_k{k}",
                     "us_per_call": 0,
                     "derived": f"{fused_s / shard_s:.2f}x"})
        k_weak = k // mesh.size
        if k_weak in _SETTINGS:
            weak_s, _, _ = _time_server(FusedPAOTA, k_weak)
            rows.append({"name": f"sharded_round/weak_k{k}",
                         "us_per_call": 0,
                         "derived": f"{weak_s / shard_s:.2f}x_of_perfect;"
                                    f"fused_k{k_weak}_s={weak_s:.4f}"})
    return rows


def run(ks=(1000, 10000), dispatch_rows: bool = False) -> list:
    """benchmarks.run entry: in-process over an accelerator's devices,
    else a child with forced host devices (``common.run_multidevice``)."""
    from benchmarks.common import run_multidevice
    return run_multidevice(
        "benchmarks.sharded_round_bench", 8,
        (["--dispatch"] if dispatch_rows else []) + [str(k) for k in ks],
        lambda: _measure(ks, dispatch_rows=dispatch_rows))


def main():
    args = sys.argv[1:]
    if "--emit" in args:                     # forced-device child
        dispatch_rows = "--dispatch" in args
        args = [a for a in args if a != "--dispatch"]
        i = args.index("--emit")
        out_path, ks = args[i + 1], tuple(int(k) for k in args[i + 2:])
        rows = _measure(ks, dispatch_rows=dispatch_rows)
        with open(out_path, "w") as f:
            json.dump(rows, f)
        return
    smoke = "smoke" in args
    ks = (16,) if smoke else (1000, 10000)
    rows = run(ks=ks, dispatch_rows=smoke)
    print("name,us_per_call,derived")
    for row in rows:
        print(f"{row['name']},{row['us_per_call']},{row['derived']}",
              flush=True)
    from benchmarks.common import write_bench_artifact
    name = "sharded_round_smoke" if smoke else "sharded_round"
    # device_count in the artifact header reflects THIS (parent) process;
    # the measurements ran in the forced-device child — record that too
    path = write_bench_artifact(name, rows,
                                extra={"ks": list(ks), "forced_devices": 8})
    print(f"# artifact -> {path}", flush=True)


if __name__ == "__main__":
    main()
