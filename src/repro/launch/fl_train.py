"""Federated-learning launcher — the paper's experiment driver (Section IV).

Thin CLI over examples/fl_noniid_mnist.py:

    PYTHONPATH=src python -m repro.launch.fl_train --rounds 100 \
        --clients 100 --solver waterfill --engine batched

``--engine batched`` (default) runs local training as one jitted
vmap/scan call over the whole federation; ``--engine legacy`` restores
the seed's per-client loop (see EXPERIMENTS.md §Batched federation
engine); ``--engine fused`` runs the ENTIRE PAOTA round on-device
(repro.fl.fused.FusedPAOTA — scheduler, eq.-25 factors, water-filling P2,
channel + power cap, AirComp, broadcast and local training as one jitted
lax.scan step; see EXPERIMENTS.md §Fused PAOTA round); ``--engine
sharded`` runs the same round scanned under ``jax.shard_map`` over the
mesh client axis (repro.fl.sharded.ShardedPAOTA — per-client stages
parallel across devices, AirComp/P2 as psums; needs a multi-device
backend, e.g. XLA_FLAGS=--xla_force_host_platform_device_count=8 on CPU;
a --clients count the devices don't divide pads with masked phantom
clients; see EXPERIMENTS.md §Sharded PAOTA round).

``--params-mode pytree`` makes the fused/sharded drivers carry the model
as its native params pytree instead of a raveled vector (EXPERIMENTS.md
§Pytree round core) — the path that places transformer/MoE client leaves
via ``repro.sharding.rules.stack_client_specs``.

``--pending-dtype bfloat16`` stores the fused/sharded carry's (K, ...)
pending/delta planes in bf16 — half the K x d working set for giant-model
clients; every reduction accumulates f32 and the globals stay f32
(EXPERIMENTS.md §Round perf).

``--group-period N`` (sharded, on a ("pod", "data") mesh from
``repro.launch.mesh.make_pod_mesh``) turns on multi-pod grouped
aggregation: intra-pod partial superpositions every period, ONE cross-pod
model-sized psum per N-period window, held partials staleness-weighted
per eq. 25 (EXPERIMENTS.md §Multi-pod grouped aggregation).

``--cohort-size m`` (fused/sharded) runs the active-cohort round: model
rows exist only for the m in-flight slots. ``--compress topk|randmask``
with ``--compress-ratio s/d`` additionally sparsifies the slot payloads
to (m, s) compressed planes with per-client error-feedback residuals
(``--no-error-feedback`` drops them), superposed by the fused
gather-superpose-decompress kernel — the dense (m, d) plane never
materializes (EXPERIMENTS.md §Compressed cohort payloads).

``--tp T`` (sharded + ``--params-mode pytree``) turns on intra-client
tensor parallelism: the mesh becomes ("pod", "data", "tp") with the tp
extent taken off the client axis, and every client replica's stacked
payload leaves TP-shard their model dims over it (per-device model-plane
carry ~1/T). The round's tree reductions psum TP partials, the AWGN
realization is drawn at full leaf shapes so every TP layout consumes the
same total noise, and the compiled program keeps exactly ONE cross-client
model-sized psum — it gathers the TP blocks in the same op
(EXPERIMENTS.md §Intra-client TP).

Fault tolerance (fused/sharded; EXPERIMENTS.md §Fault tolerance):
``--faults 'nan:0.05,start:1'`` injects counter-RNG client faults — NaN/
+Inf payload rows (``nan:``/``inf:``), Byzantine-scaled deltas (``byz:``
+ ``scale:``), deep-fade channel outliers (``fade:`` + ``gain:``), pod
blackouts in grouped sharded mode (``pods:0|2`` + ``bstart:``/
``bstop:``). ``--screen`` masks corrupt uploads out of the superposition
(per-row containment, still ONE cross-client psum) with an optional
``--screen-max-norm`` Byzantine fence; ``--divergence-factor F`` rolls
the global back to the last-good slot on a post-update norm jump beyond
F. ``--checkpoint-every N`` snapshots the FULL round carry every N
rounds (``--checkpoint-dir``); ``--resume PATH`` restores one and
continues the killed run bit-for-bit (counter RNG replays identical
streams).
"""
from examples.fl_noniid_mnist import main
from repro.launch.compile_cache import enable_compile_cache

if __name__ == "__main__":
    enable_compile_cache()
    main()
