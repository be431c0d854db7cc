"""Intra-client TP under the sharded round: the ("pod","data","tp") mesh.

The perf headline of the TP topology: federating a model whose per-device
carry footprint drops ~1/TP because the stacked (K_local, ...) pending
and deltas planes TP-shard their model dims, while wall-clock stays in
the same regime (the round adds only small tp-spanning stats psums; the
one cross-client model-sized all-reduce now also gathers the TP blocks).

Two tiers, both in a forced-8-host-device subprocess (the mesh must
exist before jax initializes in the parent):

* ``smoke`` — the hidden-128 MLP federation (d = 118,281), K=8, executed
  across tp in {1, 2, 4} on meshes (1,2) / (1,2,2) / (1,2,4). The DATA
  extent is pinned at 2 (k_local = 4 on every rung) so the TP ladder
  scales the device pool 2 -> 4 -> 8 and the per-device carry drop is
  the TP split itself, not client resharding in disguise. Rows record
  amortized seconds/round, per-device payload-plane bytes
  (pending + deltas, ``addressable_shards[0]``), and the compiled
  collective structure (exactly ONE cross-client model-sized all-reduce,
  which spans the tp axis too).
* ``full`` — the minicpm-2b-reduced transformer client federation
  (pytree mode, name-based TP placement; every REDUCED model dim divides
  4), same tp ladder, executed. This is the acceptance artifact:
  ``BENCH_tp_round.json`` shows per-device carry bytes falling ~1/TP.

``python -m benchmarks.tp_round_bench smoke`` writes
``BENCH_tp_round_smoke.json`` (CI_FULL tier; gated by the >2x diff like
every other tracked artifact); ``... full`` writes ``BENCH_tp_round.json``.
"""
from __future__ import annotations

import json
import sys
import time

MODEL_SIZE_FLOOR = 4097     # above the 4096 water-filling grid psum
_TP_LADDER = (1, 2, 4)
_DEVICES = 8
_ROUNDS = {"smoke": 12, "full": 6}


def _clients_mlp(k: int = 8, seed: int = 0):
    from repro.data.partition import partition_noniid
    from repro.data.pipeline import build_federation
    from repro.data.synthetic import make_mnist_like
    from repro.fl import FLClient
    from repro.models.mlp import mlp_loss
    x, y, _, _ = make_mnist_like(n_train=2000, n_test=10, seed=1234)
    parts = partition_noniid(y, n_clients=k, seed=seed)
    return [FLClient(d, mlp_loss, batch_size=32, lr=0.1, local_steps=5)
            for d in build_federation(x, y, parts)]


def _clients_transformer(cfg, k: int = 8, n: int = 8, seq: int = 16):
    import numpy as np
    from repro.data.pipeline import ClientData
    from repro.fl import FLClient
    from repro.models.transformer import loss_fn
    rng = np.random.default_rng(0)

    def tloss(p, batch):
        return loss_fn(p, {"tokens": batch["x"]}, cfg)[0]

    return [FLClient(ClientData(
        rng.integers(0, cfg.vocab_size, (n, seq)).astype(np.int32),
        np.zeros(n, np.int32), i), tloss, batch_size=4, lr=0.01,
        local_steps=2) for i in range(k)]


def _make_server(tier: str, tp: int, seed: int = 0):
    import jax
    from repro.core import ChannelConfig, SchedulerConfig
    from repro.fl import PAOTAConfig, ShardedPAOTA
    from repro.launch.mesh import make_pod_mesh
    # data extent pinned: every rung keeps k_local = K/2, so per-device
    # payload bytes isolate the TP split (tp=1 uses 2 of the 8 devices)
    mesh = make_pod_mesh(pods=1, data=2, tp=tp)
    if tier == "smoke":
        from repro.models.mlp import init_mlp_params
        params = init_mlp_params(jax.random.PRNGKey(seed), hidden=128)
        clients, cfg = _clients_mlp(seed=seed), None
    else:
        from repro.configs.minicpm_2b import REDUCED as cfg
        from repro.models.transformer import init_model
        params = init_model(jax.random.PRNGKey(seed), cfg)
        clients = _clients_transformer(cfg)
    return ShardedPAOTA(params, clients, ChannelConfig(),
                        SchedulerConfig(n_clients=len(clients), seed=seed),
                        PAOTAConfig(seed=seed), mesh=mesh,
                        params_mode="pytree", model_cfg=cfg), mesh


def _payload_bytes_per_device(srv) -> int:
    """Per-device bytes of the model-plane carry (pending + deltas): the
    footprint the TP split is supposed to divide."""
    import jax
    total = 0
    for plane in (srv._carry.pending, srv._carry.deltas):
        if plane is None:
            continue
        for leaf in jax.tree_util.tree_leaves(plane):
            total += leaf.addressable_shards[0].data.nbytes
    return total


def _collective_counts(srv, mesh, rounds: int):
    from repro.launch.collectives import axis_crossing_allreduce_count
    hlo = srv.compiled_scan_hlo(rounds)
    shape = tuple(mesh.shape[a] for a in mesh.axis_names)
    names = mesh.axis_names
    client_dims = tuple(i for i, a in enumerate(names) if a != "tp")
    cross_client = axis_crossing_allreduce_count(
        hlo, shape, client_dims, min_elements=MODEL_SIZE_FLOOR)
    if "tp" in names:
        tp_dims = (names.index("tp"),)
        cross_tp = axis_crossing_allreduce_count(
            hlo, shape, tp_dims, min_elements=MODEL_SIZE_FLOOR)
        small_tp = axis_crossing_allreduce_count(
            hlo, shape, tp_dims, max_elements=MODEL_SIZE_FLOOR - 1)
    else:
        cross_tp, small_tp = 0, 0
    return cross_client, cross_tp, small_tp


def _measure(tier: str) -> list:
    import numpy as np
    rounds = _ROUNDS[tier]
    rows = []
    bytes_at = {}
    for tp in _TP_LADDER:
        t0 = time.perf_counter()
        srv, mesh = _make_server(tier, tp)
        srv.advance(rounds)
        setup = time.perf_counter() - t0
        t0 = time.perf_counter()
        srv.advance(rounds)
        sec = (time.perf_counter() - t0) / rounds
        assert np.isfinite(srv.global_vec).all()
        pdev = _payload_bytes_per_device(srv)
        bytes_at[tp] = pdev
        cross_client, cross_tp, small_tp = _collective_counts(
            srv, mesh, rounds)
        # the structural contract: ONE cross-client model-sized psum,
        # and at tp > 1 that same op spans the tp axis (gather folded in)
        assert cross_client == 1, (tp, cross_client)
        if tp > 1:
            assert cross_tp == 1, (tp, cross_tp)
        rows.append({
            "name": f"tp_round/{tier}_tp{tp}",
            "us_per_call": round(sec * 1e6, 1),
            "derived": f"rounds_per_sec={1.0 / sec:.3f};"
                       f"scan_rounds={rounds};setup_s={setup:.2f};"
                       f"payload_bytes_per_device={pdev};"
                       f"cross_client_big_allreduce={cross_client};"
                       f"tp_spanning_big_allreduce={cross_tp};"
                       f"tp_spanning_small_allreduce={small_tp};"
                       f"mesh={'x'.join(str(mesh.shape[a]) for a in mesh.axis_names)}"})
    for tp in _TP_LADDER[1:]:
        rows.append({"name": f"tp_round/{tier}_bytes_ratio_tp{tp}",
                     "us_per_call": 0,
                     "derived": f"per_device_bytes_tp1_over_tp{tp}="
                                f"{bytes_at[1] / bytes_at[tp]:.2f}x"})
    return rows


def run(tier: str = "full") -> list:
    """benchmarks.run entry: in-process over an accelerator's devices,
    else a child with forced host devices (``common.run_multidevice``)."""
    from benchmarks.common import run_multidevice
    return run_multidevice("benchmarks.tp_round_bench", _DEVICES, [tier],
                           lambda: _measure(tier))


def main():
    args = sys.argv[1:]
    if "--emit" in args:                     # forced-device child
        i = args.index("--emit")
        out_path, tier = args[i + 1], args[i + 2]
        rows = _measure(tier)
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
    name = "tp_round_smoke" if tier == "smoke" else "tp_round"
    path = write_bench_artifact(
        name, rows, extra={"tp_ladder": list(_TP_LADDER),
                           "forced_devices": _DEVICES,
                           "model": ("mlp_hidden128" if tier == "smoke"
                                     else "minicpm-2b-reduced")})
    print(f"# artifact -> {path}", flush=True)


if __name__ == "__main__":
    main()
