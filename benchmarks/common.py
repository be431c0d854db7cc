"""Shared harness for the paper-reproduction benchmarks (Fig. 3/4, Table I).

Builds the federation once (synthetic MNIST-like, non-IID partition per
Section IV-A) and runs PAOTA / Local SGD / COTAF servers, recording
(round, simulated time, train loss, test accuracy) trajectories.
"""
from __future__ import annotations

import json
import os
import platform
import time
from dataclasses import dataclass
from typing import Dict, List, Optional

import jax
import numpy as np

from repro.core import BoundConstants, ChannelConfig, SchedulerConfig, contraction_A
from repro.data.partition import partition_noniid
from repro.data.pipeline import build_federation
from repro.data.synthetic import get_dataset
from repro.fl import (COTAFServer, FLClient, FusedPAOTA, LocalSGDServer,
                      PAOTAConfig, PAOTAServer, SyncConfig, evaluate)
from repro.models.mlp import init_mlp_params, mlp_apply, mlp_loss

OUT_DIR = os.environ.get("REPRO_BENCH_OUT", "experiments/bench")


def write_bench_artifact(name: str, rows: List[Dict],
                         extra: Optional[Dict] = None) -> str:
    """Persist one benchmark's rows as a machine-readable JSON artifact —
    ``<OUT_DIR>/BENCH_<name>.json`` — so the perf trajectory is tracked
    across PRs instead of scrolling away in CI logs.

    The payload carries the timing rows verbatim plus enough config to
    make numbers comparable run-to-run (backend, device count, the
    REPRO_BENCH_* env knobs). ``scripts/ci.sh`` smoke-checks one of these
    parses after the benchmark smokes. Returns the artifact path."""
    import jax
    os.makedirs(OUT_DIR, exist_ok=True)
    payload = {
        "name": name,
        "created_unix": time.time(),
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "python": platform.python_version(),
        "jax": jax.__version__,
        "env": {k: v for k, v in os.environ.items()
                if k.startswith("REPRO_BENCH")},
        "rows": rows,
    }
    if extra:
        payload["config"] = extra
    path = os.path.join(OUT_DIR, f"BENCH_{name}.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
    return path


def run_multidevice(module: str, n_devices: int, args: List[str],
                    measure) -> List[Dict]:
    """Rows of a benchmark that needs several devices.

    On an accelerator ``measure()`` runs in this process over
    ``jax.devices()``: the process holds the chips, and a child could not
    get them. On the CPU, ``module`` is re-run in a child with
    ``n_devices`` forced host devices (forcing must precede JAX's
    initialization, which this process may already have done), called as
    ``python -m <module> --emit <json> <args>``; its rows are read back."""
    import subprocess
    import sys
    import tempfile
    if jax.devices()[0].platform != "cpu":
        return measure()
    env = dict(os.environ)
    force = f"--xla_force_host_platform_device_count={n_devices}"
    if "xla_force_host_platform_device_count" not in env.get("XLA_FLAGS", ""):
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " " + force).strip()
    with tempfile.NamedTemporaryFile("r", suffix=".json") as f:
        subprocess.run([sys.executable, "-m", module, "--emit", f.name]
                       + list(args), env=env, check=True,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
        return json.load(f)


@dataclass
class BenchSetting:
    n_clients: int = 40          # paper: 100 (scaled for CPU wall-time;
    n_rounds: int = 60           # REPRO_BENCH_FULL=1 restores 100)
    n_select: int = 20           # sync baselines' participants per round
    lr: float = 0.1
    local_steps: int = 5         # M
    batch_size: int = 32
    delta_t: float = 8.0
    n0_dbm_hz: float = -174.0
    eval_every: int = 2
    seed: int = 0
    solver: str = "waterfill"
    engine: str = "batched"      # batched|legacy local-training engine, or
                                 # "fused": PAOTA runs as the on-device
                                 # lax.scan round (counter RNG), or
                                 # "sharded": the same scan under shard_map
                                 # over the mesh client axis (needs a
                                 # multi-device backend; non-divisible K
                                 # pads with masked phantom clients);
                                 # baselines fall back to the batched engine
    params_mode: str = "raveled" # fused/sharded model carry: "raveled"
                                 # (flat (K, d) stack) | "pytree" (params
                                 # tree carried natively by the round core)
    pending_dtype: str = "float32"  # fused/sharded carry storage for the
                                 # (K, ...) planes: "bfloat16" halves the
                                 # working set (f32 accumulation; globals
                                 # stay f32)
    group_period: int = 0        # sharded only: grouped aggregation window
                                 # N (0 = flat; N >= 1 = intra-pod psums
                                 # every period, ONE cross-pod psum per N
                                 # periods; the trajectory advances in
                                 # whole windows)
    cohort_size: int = 0         # fused/sharded: active-cohort mode — only
                                 # m in-flight slots carry model-sized rows
                                 # (0 = dense (K, ...) planes)
    compress: str = ""           # fused/sharded + cohort: "topk"|"randmask"
                                 # sparsifies the slot payloads to (m, s),
                                 # s = round(d * compress_ratio); forces
                                 # transmit="delta" (compression targets
                                 # the small update, not the model)
    compress_ratio: float = 1.0
    error_feedback: bool = True  # compress only: per-client residual
                                 # planes re-inject what sparsification
                                 # dropped (off = plain sparsification)
    tp: int = 1                  # sharded + pytree only: intra-client
                                 # tensor-parallel extent — the mesh gains
                                 # a "tp" axis and every client replica's
                                 # stacked payload leaves TP-shard over
                                 # it (per-device carry ~1/tp; one
                                 # clients x tp psum per round)
    faults: str = ""             # fused/sharded: fault-injection spec,
                                 # comma-separated kind:value pairs parsed
                                 # by parse_faults() — e.g.
                                 # "nan:0.05,start:1" or
                                 # "byz:0.1,scale:-50,fade:0.02"
    screen: bool = False         # fused/sharded: mask non-finite uploads
                                 # out of the superposition (containment)
    screen_max_norm: float = 0.0 # screening norm fence (0 = finite-only)
    divergence_factor: float = 0.0  # post-update rollback detector
                                 # (0 = off)
    checkpoint_every: int = 0    # fused/sharded: snapshot the full round
                                 # carry every N rounds into
                                 # checkpoint_dir (0 = off)
    checkpoint_dir: str = ""
    resume: str = ""             # fused/sharded: checkpoint path to
                                 # restore before training — the resumed
                                 # run continues the killed one bit-exactly

    @classmethod
    def from_env(cls, **kw):
        s = cls(**kw)
        if os.environ.get("REPRO_BENCH_FULL") == "1":
            s.n_clients, s.n_rounds, s.n_select = 100, 120, 50
        return s


# fault-spec keys -> FaultConfig fields ("inf" flips nan_mode, not a field)
_FAULT_KEYS = {"nan": ("nan_frac", float), "inf": ("nan_frac", float),
               "byz": ("byzantine_frac", float),
               "scale": ("byzantine_scale", float),
               "fade": ("deep_fade_frac", float),
               "gain": ("deep_fade_gain", float),
               "start": ("start", int), "stop": ("stop", int),
               "pods": ("pod_blackout", None),
               "bstart": ("blackout_start", int),
               "bstop": ("blackout_stop", int)}


def parse_faults(spec: str):
    """CLI fault spec -> ``FaultConfig``: comma-separated ``kind:value``
    pairs — ``nan:0.05`` (NaN payload fraction; ``inf:`` for +Inf rows),
    ``byz:0.1`` / ``scale:-50`` (Byzantine fraction / delta scale),
    ``fade:0.02`` / ``gain:1e-4`` (deep-fade fraction / gain),
    ``start:`` / ``stop:`` (active round window), ``pods:0|2`` /
    ``bstart:`` / ``bstop:`` (pod-blackout indices and window, grouped
    sharded mode). Empty/None spec -> None (no FaultConfig at all)."""
    from repro.core.scheduler import FaultConfig
    if not spec:
        return None
    kw = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        kind, _, val = part.partition(":")
        if kind not in _FAULT_KEYS:
            raise ValueError(f"unknown fault kind {kind!r} in {spec!r} "
                             f"(expected one of {sorted(_FAULT_KEYS)})")
        field, cast = _FAULT_KEYS[kind]
        if kind == "pods":
            kw[field] = tuple(int(p) for p in val.split("|") if p)
        else:
            kw[field] = cast(val)
        if kind == "inf":
            kw["nan_mode"] = "inf"
    return FaultConfig(**kw)


def build_world(s: BenchSetting):
    x_tr, y_tr, x_te, y_te = get_dataset(n_train=max(200 * s.n_clients, 4000),
                                         n_test=2000)
    parts = partition_noniid(y_tr, n_clients=s.n_clients, seed=s.seed)
    fed = build_federation(x_tr, y_tr, parts, seed=s.seed)
    clients = [FLClient(d, mlp_loss, batch_size=s.batch_size, lr=s.lr,
                        local_steps=s.local_steps) for d in fed]
    params = init_mlp_params(jax.random.PRNGKey(s.seed))
    return clients, params, (x_tr, y_tr, x_te, y_te)


def train_loss(params, x, y, n: int = 4096) -> float:
    import jax.numpy as jnp
    sel = np.random.default_rng(0).choice(len(y), size=min(n, len(y)),
                                          replace=False)
    return float(mlp_loss(params, {"x": jnp.asarray(x[sel]),
                                   "y": jnp.asarray(y[sel])}))


def run_algorithm(name: str, s: BenchSetting, clients, params, data,
                  seed_offset: int = 0) -> List[Dict]:
    x_tr, y_tr, x_te, y_te = data
    chan = ChannelConfig(n0_dbm_hz=s.n0_dbm_hz)
    sched = SchedulerConfig(n_clients=s.n_clients, delta_t=s.delta_t,
                            seed=s.seed + seed_offset)
    # "fused"/"sharded" are PAOTA-only modes; the sync baselines use the
    # batched engine under them so the comparison stays apples-to-apples
    engine = "batched" if s.engine in ("fused", "sharded") else s.engine
    fault_tol = (s.faults or s.screen or s.divergence_factor
                 or s.checkpoint_every or s.resume)
    if fault_tol and not (name == "paota"
                          and s.engine in ("fused", "sharded")):
        if name != "paota":
            return []       # fault-tolerance sweeps are PAOTA-only
        raise ValueError(
            "faults/screen/divergence/checkpoint knobs live on the "
            "fused/sharded drivers; pass engine='fused' or 'sharded'")
    if name == "paota":
        if s.engine in ("fused", "sharded"):
            # solver is passed through: the on-device drivers raise on
            # solvers they cannot run rather than silently substituting
            from repro.fl import ShardedPAOTA
            cls = ShardedPAOTA if s.engine == "sharded" else FusedPAOTA
            kw = {}
            if s.engine == "sharded" and s.group_period:
                kw["group_period"] = s.group_period
            if s.engine == "sharded" and s.tp > 1:
                # ("pod","data","tp") mesh: the tp extent comes off the
                # client axis (the server refuses raveled mode itself)
                import jax
                from repro.launch.mesh import make_pod_mesh
                kw["mesh"] = make_pod_mesh(
                    pods=1, data=max(len(jax.devices()) // s.tp, 1),
                    tp=s.tp)
            if s.cohort_size:
                kw["cohort_size"] = s.cohort_size
            transmit = "model"
            if s.compress:
                # compressed slots ride the delta transmit mode (the
                # drivers refuse otherwise)
                transmit = "delta"
                kw.update(compress=s.compress,
                          compress_ratio=s.compress_ratio,
                          error_feedback=s.error_feedback)
            if s.faults:
                kw["faults"] = parse_faults(s.faults)
            if s.screen:
                kw.update(screen=True, screen_max_norm=s.screen_max_norm)
            if s.divergence_factor:
                kw["divergence_factor"] = s.divergence_factor
            if s.checkpoint_every:
                kw.update(checkpoint_every=s.checkpoint_every,
                          checkpoint_dir=s.checkpoint_dir
                          or os.path.join(OUT_DIR, "checkpoints"))
            srv = cls(params, clients, chan, sched,
                      PAOTAConfig(solver=s.solver, seed=s.seed,
                                  transmit=transmit),
                      params_mode=s.params_mode,
                      pending_dtype=s.pending_dtype, **kw)
            if s.resume:
                done = srv.restore_checkpoint(s.resume)
                print(f"resumed {name} from {s.resume} (round {done})")
        else:
            srv = PAOTAServer(params, clients, chan, sched,
                              PAOTAConfig(solver=s.solver, seed=s.seed,
                                          engine=engine))
    elif name == "local_sgd":
        srv = LocalSGDServer(params, clients, sched,
                             SyncConfig(n_select=s.n_select, seed=s.seed,
                                        engine=engine))
    elif name == "cotaf":
        srv = COTAFServer(params, clients, sched,
                          SyncConfig(n_select=s.n_select, seed=s.seed,
                                     engine=engine), chan)
    else:
        raise ValueError(name)

    rows = []
    t0 = time.time()
    grouped = (name == "paota" and s.engine == "sharded"
               and s.group_period > 1)
    pending: List[Dict] = []
    for r in range(s.n_rounds):
        if grouped:
            # grouped aggregation advances in whole windows; buffer the
            # window's per-round rows and drain one per loop iteration
            if not pending:
                pending = list(srv.advance(s.group_period))
            info = pending.pop(0)
        else:
            info = srv.round()
        if r % s.eval_every == 0 or r == s.n_rounds - 1:
            gp = srv.global_params()
            ev = evaluate(gp, x_te, y_te, mlp_apply)
            rows.append({
                "algo": name, "round": info["round"],
                "time": round(info["time"], 2),
                "loss": round(train_loss(gp, x_tr, y_tr), 4),
                "accuracy": round(ev["accuracy"], 4),
                "test_loss": round(ev["loss"], 4),
                "wall_s": round(time.time() - t0, 1),
            })
    return rows
