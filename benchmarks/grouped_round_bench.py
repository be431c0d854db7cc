"""Multi-pod grouped aggregation: the ("pod", "data") sharded round.

Two tiers, both in a forced-host-device subprocess (the pod mesh must
exist before jax initializes in the parent):

* ``smoke`` — 8 virtual devices as a (2, 4) pod mesh, K=16, N=2.
  Executes amortized window scans (flat vs grouped on the SAME mesh) so
  the grouped path stays compiling-and-running in CI, and pins the
  compiled collective structure: exactly ONE cross-pod model-sized
  all-reduce per N-period window (``repro.launch.collectives`` over the
  compiled HLO).
* ``full`` — 512 virtual devices as the paper-scale (2, 256) pod mesh,
  K=10000, N=4, launch.dryrun-style: lower + compile ONLY (executing
  10k-client rounds on 512 virtual devices sharing 2 physical cores is
  not a measurement of anything). Rows record lower/compile wall time
  and the same cross-pod collective count.

The model-size floor separates the d+1 grouped psums (default MLP:
8071 elements) from the water-filling grid (4096) and the combiner-merged
scalar metrics — same role as the 8192 default in ``collectives``, placed
under this model's size.

``python -m benchmarks.grouped_round_bench smoke`` writes
``BENCH_grouped_round_smoke.json`` (CI_FULL tier; gated by the >2x diff
like every other tracked artifact); ``... full`` writes
``BENCH_grouped_round.json``.
"""
from __future__ import annotations

import json
import sys
import time

MODEL_SIZE_FLOOR = 4097
_SETTINGS = {          # K -> (size ladder, batch, local steps, scan rounds)
    16: ((48, 64), 32, 5, 24),
    10000: ((16, 24), 16, 2, 8),
}
_TIERS = {             # tier -> (K, group_period, (pods, data))
    "smoke": (16, 2, (2, 4)),
    "full": (10000, 4, (2, 256)),
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


def _make_server(k: int, mesh, group_period: int, seed: int = 0):
    import jax
    from repro.core import ChannelConfig, SchedulerConfig
    from repro.fl import PAOTAConfig, ShardedPAOTA
    from repro.models.mlp import init_mlp_params
    params = init_mlp_params(jax.random.PRNGKey(seed))
    return ShardedPAOTA(params, _make_engine(k, seed), ChannelConfig(),
                        SchedulerConfig(n_clients=k, seed=seed),
                        PAOTAConfig(seed=seed), mesh=mesh,
                        group_period=group_period)


def _collective_rows(srv, mesh, k: int, n: int, scan_rounds: int) -> list:
    """The structural row: cross-pod / intra-pod model-sized all-reduce
    counts in the compiled scan body (one window when grouped)."""
    from repro.launch.collectives import (cross_pod_allreduce_count,
                                          iter_allreduces)
    t0 = time.perf_counter()
    hlo = srv.compiled_scan_hlo(scan_rounds)
    compile_s = time.perf_counter() - t0
    shape = tuple(mesh.shape[a] for a in mesh.axis_names)
    cross = cross_pod_allreduce_count(hlo, shape, (0,),
                                      min_elements=MODEL_SIZE_FLOOR)
    big = sum(1 for sz, _ in iter_allreduces(hlo)
              if sz >= MODEL_SIZE_FLOOR)
    assert cross == 1, (cross, big)       # the grouped contract
    assert big == n, (cross, big)         # N-1 intra-pod partials + 1 sync
    return [{"name": f"grouped_round/collectives_k{k}_n{n}"
                     f"_pods{shape[0]}",
             "us_per_call": round(compile_s * 1e6, 1),
             "derived": f"cross_pod_big_allreduce_per_window={cross};"
                        f"big_allreduce_per_window={big};"
                        f"model_size_floor={MODEL_SIZE_FLOOR};"
                        f"lower_compile_s={compile_s:.2f}"}]


def _measure_smoke() -> list:
    """8 virtual devices: run flat and grouped window scans on the same
    (2, 4) pod mesh; amortized seconds/round over the chunked scan."""
    import numpy as np
    from repro.launch.mesh import make_pod_mesh
    k, n, (pods, data) = _TIERS["smoke"]
    rounds = _SETTINGS[k][3]
    mesh = make_pod_mesh(pods=pods, data=data)
    rows = []
    secs = {}
    grouped_srv = None
    for label, period in (("flat", 0), (f"grouped_n{n}", n)):
        t0 = time.perf_counter()
        srv = _make_server(k, mesh, period)
        srv.advance(rounds)
        setup = time.perf_counter() - t0
        t0 = time.perf_counter()
        srv.advance(rounds)
        sec = (time.perf_counter() - t0) / rounds
        secs[label] = sec
        assert np.isfinite(srv.global_vec).all()
        if period:
            grouped_srv = srv
        rows.append({"name": f"grouped_round/{label}_k{k}_pods{pods}",
                     "us_per_call": round(sec * 1e6, 1),
                     "derived": f"rounds_per_sec={1.0 / sec:.3f};"
                                f"scan_rounds={rounds};"
                                f"setup_s={setup:.2f}"})
    rows.append({"name": f"grouped_round/grouped_vs_flat_k{k}",
                 "us_per_call": 0,
                 "derived": f"{secs['flat'] / secs[f'grouped_n{n}']:.2f}x"})
    rows += _collective_rows(grouped_srv, mesh, k, n, rounds)
    return rows


def _measure_full() -> list:
    """512 virtual devices, K=10000, N=4 — dryrun-style: construction +
    lower + compile of the grouped window scan, no execution."""
    from repro.launch.mesh import make_pod_mesh
    k, n, (pods, data) = _TIERS["full"]
    rounds = _SETTINGS[k][3]
    mesh = make_pod_mesh(pods=pods, data=data)
    t0 = time.perf_counter()
    srv = _make_server(k, mesh, n)
    setup = time.perf_counter() - t0
    rows = _collective_rows(srv, mesh, k, n, rounds)
    rows[0]["derived"] += (f";setup_s={setup:.2f};k_pad={srv.k_pad};"
                           f"k_local={srv.k_local};devices={mesh.size};"
                           f"dryrun=lower_compile_only")
    return rows


def run(tier: str = "full") -> list:
    """benchmarks.run entry: in-process over an accelerator's devices,
    else a child with the tier's forced host device count
    (``common.run_multidevice``)."""
    from benchmarks.common import run_multidevice
    _, _, (pods, data) = _TIERS[tier]
    return run_multidevice(
        "benchmarks.grouped_round_bench", pods * data, [tier],
        _measure_smoke if tier == "smoke" else _measure_full)


def main():
    args = sys.argv[1:]
    if "--emit" in args:                     # forced-device child
        i = args.index("--emit")
        out_path, tier = args[i + 1], args[i + 2]
        rows = _measure_smoke() if tier == "smoke" else _measure_full()
        with open(out_path, "w") as f:
            json.dump(rows, f)
        return
    tier = "full" if "full" in args else "smoke"
    rows = run(tier)
    print("name,us_per_call,derived")
    for row in rows:
        print(f"{row['name']},{row['us_per_call']},{row['derived']}",
              flush=True)
    from benchmarks.common import write_bench_artifact
    k, n, (pods, data) = _TIERS[tier]
    name = "grouped_round_smoke" if tier == "smoke" else "grouped_round"
    path = write_bench_artifact(
        name, rows, extra={"k": k, "group_period": n,
                           "mesh": {"pod": pods, "data": data},
                           "forced_devices": pods * data})
    print(f"# artifact -> {path}", flush=True)


if __name__ == "__main__":
    main()
