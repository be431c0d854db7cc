#!/usr/bin/env python3
"""Bring-up smoke of the PAOTA round on a TPU.

    python chip_smoke.py            # one chip: phases (a), (b), (c)
    python chip_smoke.py --chips 4  # four chips: the sharded paper
                                    # federation against the fused one

One chip, three phases in one process, each driven through the entry
points a user calls (``FusedPAOTA(...).advance``), built the way
``benchmarks.common.run_algorithm`` builds them:

(a) The paper federation (Sec. IV-A): MLP 784-10-10-10, K=100, M=5 local
    steps, B=32, delta_t=8, water-filling P2, model transmit; 10 periods
    in one ``advance``, raveled and pytree. Reference: the host
    ``PAOTAServer(rng="counter", solver="waterfill_jnp")`` on the same
    seeds, on this process's CPU device.
(b) The compressed cohort: K=100, m=32, randmask s/d=1/16, int8 slots,
    10 periods (the gather-superpose kernel). Reference: the same program
    on the CPU device.
(c) A smollm-135m client at published widths (30 layers, d_model 576,
    9/3 heads, d_ff 1536, vocab 49152): pytree ``FusedPAOTA``, K=4, 2x128
    token local batches, M=1, bf16 pending planes, 2 periods. Reference:
    ``repro.kernels.ref`` at HIGHEST precision for the round stats and the
    superposition over the run's own payload planes.

``--chips 4`` runs the paper federation on a (4, 1) client mesh
(``ShardedPAOTA``, raveled and pytree) against ``FusedPAOTA`` on the
first of those chips, and counts the cross-client model-sized
all-reduces in the compiled scan (the contract is one).

Every time printed is a bring-up reading of one run, not a benchmark.
The script refuses to run where JAX finds no TPU. Its last line of
standard output is one JSON object: ``{"ok": ..., "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
PERIODS = 10

# Tolerances of the TPU run against its reference, in the units of the
# global model's coordinates (|w| < ~2 here).
#
# (a) TPU at HIGHEST matmul precision (f32 through six bf16 passes)
#     against the CPU's f32: per-op differences are f32 rounding and
#     transcendental-approximation noise (~1e-7 relative). The P2
#     objective is flat near its optimum, so such noise can move the
#     water-filling solution by a grid cell; on the CPU two summation
#     orders of the same federation end 2e-5 apart after 6 periods
#     (tests/test_cohort_round.py). Over 10 periods of 5 local steps the
#     bound atol + rtol * |w| (1e-4 plus 1e-3 of the coordinate) leaves
#     room for several such cells; a v5e ended 1.267e-4 from the host.
PAPER_ATOL, PAPER_RTOL = 1e-4, 1e-3
# (b) int8 slots with stochastic rounding: a value one ulp apart on the
#     two platforms can round to the neighbouring int8 level, which moves
#     that coordinate of the aggregate by one quantum (row absmax / 127,
#     times the slot's weight b_k p_k / varsigma <= 1). Update rows here
#     are below 0.05 in absmax, so a quantum is below 4e-4.
COHORT_ATOL, COHORT_RTOL = 1e-3, 1e-3
# (c) kernel against ref at HIGHEST: both accumulate in f32 over up to
#     2.8e7 terms in different orders; bf16 payload products are exact in
#     f32. Relative error of a sum of squares <= n * eps in the worst
#     case, ~sqrt(n) * eps in practice: 1e-4 relative bounds the norms,
#     dots are held to 1e-4 * ||delta|| * ||g|| (Cauchy-Schwarz scale).
STATS_RTOL = 1e-4
AGG_RTOL = 1e-5      # aggregate error relative to max |payload| (K=4 sum)
# --chips 4: sharded and fused on the same chips and precision differ by
# the order of the psum'd reductions only (the suite's sharded-vs-fused
# test holds them to rtol 1e-4, atol 1e-5 on the CPU after 4 rounds;
# 10 periods through the flat P2 get the paper tolerance).
SHARDED_ATOL, SHARDED_RTOL = PAPER_ATOL, PAPER_RTOL
MODEL_SIZE_FLOOR = 4097  # above the water-filling grid's 4096-wide psum


class Phase:
    """Prints one phase's lines and collects its verdicts."""

    def __init__(self, name: str):
        self.name, self.ok = name, True

    def line(self, **kv):
        print(f"[{self.name}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
              flush=True)

    def check(self, what: str, passed: bool, **kv):
        self.line(check=what, passed=bool(passed), **kv)
        self.ok &= bool(passed)


def _peak_bytes(dev):
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use", "not reported")


def _max_abs(a, b):
    return float(np.max(np.abs(np.asarray(a, np.float64)
                               - np.asarray(b, np.float64))))


def _close(a, b, atol, rtol):
    return bool(np.allclose(np.asarray(a), np.asarray(b), atol=atol,
                            rtol=rtol))


def _paper_setting(k: int = 100):
    from benchmarks.common import BenchSetting
    return BenchSetting(n_clients=k, local_steps=5, batch_size=32,
                        delta_t=8.0, solver="waterfill_jnp", engine="fused")


def _fused(s, clients, params, cls=None, transmit="model", **kw):
    """The on-device driver exactly as ``run_algorithm`` builds it."""
    from repro.core import ChannelConfig, SchedulerConfig
    from repro.fl import FusedPAOTA, PAOTAConfig
    cls = cls or FusedPAOTA
    return cls(params, clients, ChannelConfig(n0_dbm_hz=s.n0_dbm_hz),
               SchedulerConfig(n_clients=s.n_clients, delta_t=s.delta_t,
                               seed=s.seed),
               PAOTAConfig(solver=s.solver, seed=s.seed, transmit=transmit),
               **kw)


def _timed_advance(ph: Phase, srv, periods: int, dev, run: str = "fused"):
    """Compile the ``periods``-period scan, run it once through
    ``advance`` (the compared run), then once more for a steady reading.
    Returns the rows and globals of the compared run."""
    import jax
    t0 = time.perf_counter()
    compiled = srv.compile_scan(periods)
    compile_s = time.perf_counter() - t0
    n_kernels = compiled.as_text().count("tpu_custom_call")
    t0 = time.perf_counter()
    rows = srv.advance(periods)
    jax.block_until_ready(srv._carry)
    first_s = time.perf_counter() - t0
    glob = srv.global_vec.copy()
    t0 = time.perf_counter()
    srv.advance(periods)
    jax.block_until_ready(srv._carry)
    per_period = (time.perf_counter() - t0) / periods
    ph.line(run=run, device_kind=repr(dev.device_kind),
            compile_s=f"{compile_s:.2f}", first_advance_s=f"{first_s:.2f}",
            bringup_s_per_period=f"{per_period:.6f}",
            peak_bytes_in_use=_peak_bytes(dev), tpu_custom_call=n_kernels)
    ph.check(f"kernels_in_scan[{run}]", n_kernels > 0,
             tpu_custom_call=n_kernels)
    return rows, glob, compiled


def _stepwise_masks(srv, periods: int):
    """Per-period uploader masks of a fused driver: an uploader of period
    r receives w_g^{r+1}, so its carried model round becomes r + 1."""
    masks = []
    for r in range(periods):
        srv.advance(1)
        masks.append(np.asarray(srv._carry.model_round) == r + 1)
    return np.stack(masks)


def _host_reference(s, clients, params, cpu, periods: int):
    """The host PAOTAServer in counter-RNG mode on the CPU device: its
    per-period uploader masks and final global."""
    import jax
    from repro.core import ChannelConfig, SchedulerConfig
    from repro.fl import PAOTAConfig, PAOTAServer
    with jax.default_device(cpu):
        host = PAOTAServer(
            jax.device_put(params, cpu), clients,
            ChannelConfig(n0_dbm_hz=s.n0_dbm_hz),
            SchedulerConfig(n_clients=s.n_clients, delta_t=s.delta_t,
                            seed=s.seed, rng="counter"),
            PAOTAConfig(rng="counter", solver="waterfill_jnp", seed=s.seed))
        masks = []
        advance = host.scheduler.advance_to_aggregation

        def recording_advance():
            upl, stal = advance()
            m = np.zeros(s.n_clients, bool)
            m[upl] = True
            masks.append(m)
            return upl, stal

        host.scheduler.advance_to_aggregation = recording_advance
        rows = [host.round() for _ in range(periods)]
        return rows, np.stack(masks), host.global_vec.copy()


def phase_paper(dev, cpu, k: int = 100, periods: int = PERIODS) -> bool:
    """(a) the paper federation, raveled and pytree, against the host."""
    import jax
    from benchmarks.common import build_world
    s = _paper_setting(k)
    clients, params, _ = build_world(s)
    t0 = time.perf_counter()
    h_rows, h_masks, h_glob = _host_reference(s, clients, params, cpu,
                                              periods)
    ref_s = time.perf_counter() - t0
    ok = True
    for mode in ("raveled", "pytree"):
        ph = Phase(f"a:paper-{mode}")
        ph.line(reference="host PAOTAServer(rng=counter, "
                          "solver=waterfill_jnp) on cpu",
                reference_s=f"{ref_s:.2f}",
                precision="tpu=highest cpu=f32-default")
        with jax.default_matmul_precision("highest"):
            srv = _fused(s, clients, params, params_mode=mode)
            rows, glob, _ = _timed_advance(ph, srv, periods, dev)
            masks = _stepwise_masks(_fused(s, clients, params,
                                           params_mode=mode), periods)
        ph.check("masks_bit_equal", np.array_equal(masks, h_masks),
                 uploads=int(h_masks.sum()))
        ph.check("participants_equal",
                 [r["n_participants"] for r in rows]
                 == [r["n_participants"] for r in h_rows])
        ph.check("global_vs_host", _close(glob, h_glob, PAPER_ATOL,
                                          PAPER_RTOL),
                 max_abs_diff=f"{_max_abs(glob, h_glob):.3e}",
                 atol=PAPER_ATOL, rtol=PAPER_RTOL)
        ok &= ph.ok
    return ok


def phase_cohort(dev, cpu, k: int = 100, m: int = 32,
                 periods: int = PERIODS) -> bool:
    """(b) the compressed int8 cohort against the same program on CPU."""
    import jax
    from benchmarks.common import build_world
    s = _paper_setting(k)
    clients, params, _ = build_world(s)
    kw = dict(transmit="delta", cohort_size=m, compress="randmask",
              compress_ratio=1.0 / 16.0, slot_dtype="int8")
    ph = Phase("b:cohort-randmask16-int8")
    ph.line(reference="same FusedPAOTA program on cpu",
            precision="tpu=highest cpu=f32-default")
    with jax.default_matmul_precision("highest"):
        srv = _fused(s, clients, params, **kw)
        rows, glob, _ = _timed_advance(ph, srv, periods, dev)
    with jax.default_device(cpu):
        ref = _fused(s, clients, jax.device_put(params, cpu), **kw)
        r_rows = ref.advance(periods)
        r_glob = ref.global_vec.copy()
    ph.check("participants_equal",
             [r["n_participants"] for r in rows]
             == [r["n_participants"] for r in r_rows],
             uploads=sum(r["n_participants"] for r in r_rows))
    ph.check("global_vs_cpu", _close(glob, r_glob, COHORT_ATOL,
                                     COHORT_RTOL),
             max_abs_diff=f"{_max_abs(glob, r_glob):.3e}",
             atol=COHORT_ATOL, rtol=COHORT_RTOL)
    return ph.ok


def _transformer_clients(cfg, k: int, n_seq: int = 8, seq: int = 128,
                         batch: int = 2):
    from repro.data.pipeline import ClientData
    from repro.fl import FLClient
    from repro.models.transformer import loss_fn
    rng = np.random.default_rng(0)

    def tloss(p, b):
        return loss_fn(p, {"tokens": b["x"]}, cfg)[0]

    return [FLClient(ClientData(
        rng.integers(0, cfg.vocab_size, (n_seq, seq)).astype(np.int32),
        np.zeros(n_seq, np.int32), i), tloss, batch_size=batch, lr=0.01,
        local_steps=1) for i in range(k)]


def _kernel_vs_ref(ph: Phase, carry, tag: str):
    """The round-stats sweep and the superposition over the carry's own
    payload planes: the ops the round ran (kernels on the TPU) against
    ``repro.kernels.ref`` at HIGHEST precision."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref

    deltas, payload = carry.deltas, carry.pending
    gdir = jax.tree_util.tree_map(jnp.subtract, carry.global_vec,
                                  carry.prev_global)

    @jax.jit
    def sweep(deltas, payload, gdir):
        dots, dn2, pn2, gn2 = ops.round_stats(deltas, gdir, payload)
        with jax.default_matmul_precision("highest"):
            parts = [ref.round_stats_ref(d.reshape(d.shape[0], -1),
                                         g.reshape(-1),
                                         p.reshape(p.shape[0], -1))
                     for d, p, g in zip(jax.tree_util.tree_leaves(deltas),
                                        jax.tree_util.tree_leaves(payload),
                                        jax.tree_util.tree_leaves(gdir))]
        stats = sum(p[0] for p in parts)
        return (dots, dn2, pn2, gn2), (stats[:, 0], stats[:, 1],
                                       stats[:, 2], sum(p[1] for p in parts))

    (dots, dn2, pn2, gn2), (r_dots, r_dn2, r_pn2, r_gn2) = jax.device_get(
        sweep(deltas, payload, gdir))
    scale = np.sqrt(np.maximum(r_dn2, 1e-30) * max(float(r_gn2), 1e-30))
    norm_err = max(float(np.max(np.abs(dn2 - r_dn2) / r_dn2)),
                   float(np.max(np.abs(pn2 - r_pn2) / r_pn2)),
                   abs(float(gn2) - float(r_gn2)) / max(float(r_gn2), 1e-30))
    dot_err = float(np.max(np.abs(dots - r_dots) / scale))
    ph.check(f"round_stats_vs_ref[{tag}]",
             norm_err <= STATS_RTOL and dot_err <= STATS_RTOL,
             norm_rel_err=f"{norm_err:.3e}", dot_err_over_cs=f"{dot_err:.3e}",
             gn2=f"{float(r_gn2):.6e}", rtol=STATS_RTOL)

    leaves = jax.tree_util.tree_leaves(payload)
    key = jax.random.PRNGKey(7)
    kp, kn = jax.random.split(key)
    powers = jax.random.uniform(kp, (leaves[0].shape[0],), maxval=15.0)
    mask = jnp.ones_like(powers)

    @jax.jit
    def superpose(leaves, powers, mask):
        errs, amax = [], []
        for i, leaf in enumerate(leaves):
            x = leaf.reshape(leaf.shape[0], -1)
            noise = 1e-3 * jax.random.normal(jax.random.fold_in(kn, i),
                                             (x.shape[1],))
            agg, vs = ops.superpose_normalize(x, powers, mask, noise)
            with jax.default_matmul_precision("highest"):
                r_agg, r_vs = ref.superpose_normalize_ref(x, powers, mask,
                                                          noise)
            errs.append(jnp.max(jnp.abs(agg - r_agg)))
            amax.append(jnp.max(jnp.abs(x.astype(jnp.float32))))
        return jnp.max(jnp.stack(errs)), jnp.max(jnp.stack(amax))

    err, amax = (float(v) for v in superpose(leaves, powers, mask))
    ph.check(f"superpose_vs_ref[{tag}]", err <= AGG_RTOL * amax,
             max_abs_err=f"{err:.3e}", max_abs_payload=f"{amax:.3e}",
             rtol=AGG_RTOL)


def phase_transformer(dev, k: int = 4, periods: int = 2) -> bool:
    """(c) smollm-135m clients at published widths."""
    import jax
    from repro.configs.smollm_135m import CONFIG as cfg
    from repro.models.transformer import init_model
    s = _paper_setting(k)
    ph = Phase(f"c:smollm-135m-k{k}")
    ph.line(model=cfg.name, layers=cfg.num_layers, d_model=cfg.d_model,
            heads=f"{cfg.num_heads}/{cfg.num_kv_heads}", d_ff=cfg.d_ff,
            vocab=cfg.vocab_size, clients=k, local_batch="2x128",
            local_steps=1, pending_dtype="bfloat16",
            precision="training=default round-contractions=highest")
    params = init_model(jax.random.PRNGKey(0), cfg)
    srv = _fused(s, _transformer_clients(cfg, k), params,
                 params_mode="pytree", pending_dtype="bfloat16")
    srv._ensure_carry()
    _kernel_vs_ref(ph, srv._carry, "period-1 inputs")
    t0 = time.perf_counter()
    compiled = srv.compile_scan(periods)
    compile_s = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    n_kernels = compiled.as_text().count("tpu_custom_call")
    # the donated carry aliases the scan's output: count those bytes once
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    limit = (dev.memory_stats() or {}).get("bytes_limit")
    ph.line(compile_s=f"{compile_s:.2f}",
            memory_analysis=f"args={mem.argument_size_in_bytes} "
                            f"out={mem.output_size_in_bytes} "
                            f"alias={mem.alias_size_in_bytes} "
                            f"temp={mem.temp_size_in_bytes}",
            program_bytes=need, bytes_limit=limit)
    ph.check("fits", limit is None or need <= limit, clients=k)
    t0 = time.perf_counter()
    rows = srv.advance(periods)
    jax.block_until_ready(srv._carry)
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    srv.advance(periods)
    jax.block_until_ready(srv._carry)
    per_period = (time.perf_counter() - t0) / periods
    ph.line(device_kind=repr(dev.device_kind), compile_s=f"{compile_s:.2f}",
            first_advance_s=f"{first_s:.2f}",
            bringup_s_per_period=f"{per_period:.6f}",
            peak_bytes_in_use=_peak_bytes(dev), tpu_custom_call=n_kernels)
    ph.check("kernels_in_scan", n_kernels > 0, tpu_custom_call=n_kernels)
    ph.check("finite", bool(np.isfinite(srv.global_vec).all()))
    ph.check("has_uploaders", sum(r["n_participants"] for r in rows) > 0,
             participants=[r["n_participants"] for r in rows])
    _kernel_vs_ref(ph, srv._carry, f"after {2 * periods} periods")
    return ph.ok


def phase_sharded(devs, k: int = 100, periods: int = PERIODS) -> bool:
    """--chips 4: ShardedPAOTA on a (4, 1) client mesh against FusedPAOTA
    on the first chip, raveled and pytree."""
    import jax
    from benchmarks.common import build_world
    from repro.fl import ShardedPAOTA
    from repro.launch.collectives import axis_crossing_allreduce_count
    from repro.launch.mesh import make_client_mesh
    s = _paper_setting(k)
    clients, params, _ = build_world(s)
    mesh = make_client_mesh(len(devs))
    shape = tuple(mesh.shape[a] for a in mesh.axis_names)
    ok = True
    for mode in ("raveled", "pytree"):
        ph = Phase(f"sharded:paper-{mode}-mesh{'x'.join(map(str, shape))}")
        with jax.default_matmul_precision("highest"):
            fused = _fused(s, clients, params, params_mode=mode)
            f_rows, f_glob, _ = _timed_advance(ph, fused, periods, devs[0])
            shard = _fused(s, clients, params, cls=ShardedPAOTA,
                           params_mode=mode, mesh=mesh)
            s_rows, s_glob, compiled = _timed_advance(ph, shard, periods,
                                                      devs[0], "sharded")
        big = axis_crossing_allreduce_count(
            compiled.as_text(), shape, (0,), min_elements=MODEL_SIZE_FLOOR)
        ph.check("one_cross_client_model_sized_allreduce", big == 1,
                 count=big)
        ph.check("participants_equal",
                 [r["n_participants"] for r in s_rows]
                 == [r["n_participants"] for r in f_rows])
        ph.check("sharded_vs_fused", _close(s_glob, f_glob, SHARDED_ATOL,
                                            SHARDED_RTOL),
                 max_abs_diff=f"{_max_abs(s_glob, f_glob):.3e}",
                 atol=SHARDED_ATOL, rtol=SHARDED_RTOL)
        ok &= ph.ok
    return ok


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded paper federation against "
                         "the fused one, on four chips")
    args = ap.parse_args()
    src = os.path.join(REPO, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repro package under {src}; run this script "
              f"from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path[:0] = [src, REPO]
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: JAX found no devices: {e}", file=sys.stderr)
        return 1
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devs[0].platform}",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips, "
              f"JAX found {len(devs)}", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    cpu = jax.devices("cpu")[0]
    dev = devs[0]
    phases = ([lambda: phase_sharded(devs[:4])] if args.chips == 4 else
              [lambda: phase_paper(dev, cpu), lambda: phase_cohort(dev, cpu),
               lambda: phase_transformer(dev)])
    ok = True
    for run in phases:
        try:
            ok &= run()
        except Exception:           # report the phase, go on to the next
            import traceback
            traceback.print_exc()
            ok = False
    print(json.dumps({"ok": bool(ok), "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
