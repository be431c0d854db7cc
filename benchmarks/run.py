"""Benchmark harness entry point — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:
  bound    — Theorem-1 contraction + P2 gap terms (convergence machinery)
  kernels  — aggregation/cosine/SWA kernel characteristics
  roofline — per (arch x shape x mesh) roofline terms from the dry-run
  fl_engine — legacy vs batched federation engine rounds/sec (K up to 1000)
  fused_round — host-loop vs fused lax.scan PAOTA rounds/sec (K up to 1000)
  round_perf — the canonical tracked delta-plane series: host/fused/sharded
             seconds/round at K in {16, 1000}, raveled + pytree, model +
             delta transmit (d ~= 55k MLP, 1 local step — data-plane bound)
  sharded_round — fused 1-device vs shard_map'd 8-device PAOTA rounds/sec
             (K up to 10000; runs in a subprocess with forced host devices)
  grouped_round — multi-pod grouped aggregation: K=10000 on the forced
             512-device (2, 256) pod mesh, dryrun lower+compile + the
             one-cross-pod-psum-per-window compiled-HLO collective check
  cohort_round — active-cohort (m, d) payload plane vs dense carry:
             driver + synthetic-stream rounds/sec and carry bytes at
             K in {1e3, 1e5, 1e6} (1e6 = state-plane-only acceptance run)
  tp_round — intra-client TP on the ("pod","data","tp") mesh: the
             minicpm-2b-reduced pytree federation at tp in {1, 2, 4},
             per-device carry bytes ~1/tp with ONE cross-client
             model-sized psum (compiled-HLO checked)
  fig3     — train-loss robustness vs noise (paper Fig. 3)
  fig4     — test accuracy vs rounds/time (paper Fig. 4)
  table1   — time/rounds to target accuracy (paper Table I)

Each completed module ALSO writes a machine-readable artifact —
``experiments/bench/BENCH_<module>.json`` with the rows plus backend/env
config — so perf is tracked across PRs (scripts/ci.sh smoke-checks one).

Env: REPRO_BENCH_FULL=1 for paper-scale (100 clients); default is a
CPU-friendly scaled setting with identical structure.
Select subsets: ``python -m benchmarks.run fig3 table1``
"""
from __future__ import annotations

import sys
import traceback

MODULES = ["bound", "kernels_bench", "roofline_bench", "fl_engine_bench",
           "fused_round_bench", "round_perf_bench", "sharded_round_bench",
           "grouped_round_bench", "cohort_round_bench", "tp_round_bench",
           "fig3", "fig4", "table1", "ablation"]
ALIASES = {"kernels": "kernels_bench", "roofline": "roofline_bench",
           "fl_engine": "fl_engine_bench", "engine": "fl_engine_bench",
           "fused_round": "fused_round_bench", "fused": "fused_round_bench",
           "round_perf": "round_perf_bench",
           "sharded_round": "sharded_round_bench",
           "sharded": "sharded_round_bench",
           "grouped_round": "grouped_round_bench",
           "grouped": "grouped_round_bench",
           "cohort_round": "cohort_round_bench",
           "cohort": "cohort_round_bench",
           "tp_round": "tp_round_bench",
           "tp": "tp_round_bench"}


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    wanted = sys.argv[1:] or MODULES
    wanted = [ALIASES.get(w, w) for w in wanted]
    print("name,us_per_call,derived")
    failed = []
    for mod_name in wanted:
        try:
            mod = __import__(f"benchmarks.{mod_name}", fromlist=["run"])
            rows = list(mod.run())
            for row in rows:
                print(f"{row['name']},{row['us_per_call']},{row['derived']}",
                      flush=True)
            from benchmarks.common import write_bench_artifact
            # BENCH_<name> matches what direct `python -m benchmarks.X`
            # invocation writes (the `_bench` module suffix is dropped)
            art = mod_name[:-6] if mod_name.endswith("_bench") else mod_name
            path = write_bench_artifact(art, rows)
            print(f"# artifact -> {path}", flush=True)
        except Exception:
            traceback.print_exc()
            failed.append(mod_name)
    if failed:
        print(f"# FAILED: {failed}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
