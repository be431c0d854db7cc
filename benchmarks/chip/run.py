#!/usr/bin/env python3
"""One run of one cell of the chip benchmark.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine that holds the chips the
cell asks for. The cell (``BENCHMARK.json``'s ``workloads``) names a
configuration (``configs/<name>.json``, whose ``model`` names a module of
``models/``) and a traffic mix (``traffic/<name>.json``); its correctness
limits are ``limits/<cell>.json`` and its per-layer metrics are read by
``metrics/<metric>.py``. A later cell, configuration or metric is a new
file of these kinds.

The run builds the cell's federation from the seed, warms up through the
driver's own ``advance(n)`` until a call compiles nothing (set-up), then
drives ``advance(n)`` back to back, each call ended by
``block_until_ready`` on the carry, for ``--seconds`` (``--trace 0``: the
end-to-end metrics) or traces a shorter window (``--trace 1``: the
per-layer metrics, the device busy time and the breakdown). The first
``advance(n)`` of set-up is compared with the plain reference
(``compare.py``) once the window has closed and the program's state is
freed. The last line of standard output is the result as one JSON object.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from types import SimpleNamespace

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TRACE_SECONDS = 1.0      # the traced window; at least one advance(n) call
TRAIN_SPAN_SECONDS = 0.5  # how long ``train_ms`` repeats the training call


def fail(msg: str) -> int:
    print(f"run.py: {msg}", file=sys.stderr)
    return 1


def progress(t_start: float, msg: str):
    print(f"run.py: {time.perf_counter() - t_start:.3f} s: {msg}",
          file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def load_cell(name: str):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    end_to_end = [m for m in bench["end_to_end"]
                  if name in m.get("workloads", [name])]
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name])]
    return cell, end_to_end, per_layer


def load_metric(name: str):
    spec = importlib.util.spec_from_file_location(
        f"metric_{name}", os.path.join(HERE, "metrics", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileCounter:
    """Counts the traces and backend compiles JAX reports."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _secs, **_kw):
        if event in self.EVENTS:
            self.n += 1


def check_devices(chips: int):
    """The cell's chips, or an error message where JAX finds no TPU or
    fewer chips than the cell asks for."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        return None, f"JAX found no devices: {e}"
    if devs[0].platform != "tpu":
        return None, f"needs a TPU, JAX found {devs[0].platform}"
    if len(devs) < chips:
        return None, f"the cell needs {chips} chips, JAX found {len(devs)}"
    return devs[:chips], None


def enable_compile_cache():
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        cache = os.path.join(ROOT, ".jax_cache")
        os.makedirs(cache, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def shapes_of(driver, traffic, w0, chips):
    import jax
    import numpy as np
    leaves = [int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(w0)]
    if traffic["params_mode"] == "raveled":
        leaves = [sum(leaves)]
    pend = np.dtype(traffic["pending_dtype"]).itemsize
    sh = {"rows": (traffic.get("cohort_size") or traffic["clients"]
                   // chips),
          "leaves": leaves, "delta_bytes": pend,
          "payload_bytes": pend if traffic["transmit"] == "model" else 0}
    if traffic.get("compress"):
        sh["s"] = driver.compress_s
        sh["slot_bytes"] = np.dtype(traffic.get("slot_dtype")
                                    or traffic["pending_dtype"]).itemsize
    return sh


def advance(driver, n):
    import jax
    rows = driver.advance(n)
    jax.block_until_ready(driver._carry)
    return rows


def train_span_ms(driver, traffic) -> float:
    """The driver's local-training call for one period, alone, compiled
    with the options the driver compiles its scan with."""
    import jax
    from repro.fl.fused import TPU_SCAN_OPTIONS
    c = driver._carry
    x, y = driver.engine._x, driver.engine._y
    opts = TPU_SCAN_OPTIONS if x.devices().pop().platform == "tpu" else None
    if traffic.get("cohort_size"):
        fn = jax.jit(driver._cohort_train, compiler_options=opts)
        args = (c.global_vec, x, y, c.t, c.slot_client)
    else:
        fn = jax.jit(driver._local_train_all, compiler_options=opts)
        args = (c.global_vec, x, y, c.t)
    jax.block_until_ready(fn(*args))
    calls, t0 = 0, time.perf_counter()
    while time.perf_counter() - t0 < TRAIN_SPAN_SECONDS:
        jax.block_until_ready(fn(*args))
        calls += 1
    return 1e3 * (time.perf_counter() - t0) / calls


def leaves_f64(tree):
    import jax
    import numpy as np
    return [np.asarray(a, np.float64) for a in jax.tree_util.tree_leaves(tree)]


def main(argv=None, check=check_devices) -> int:
    t_start = time.perf_counter()
    args = parse(argv)
    try:
        cell, end_to_end, per_layer = load_cell(args.workload)
    except (OSError, KeyError) as e:
        return fail(str(e))
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        return fail(f"no program under {ROOT}/src; run from a checkout of "
                    f"the repository")
    sys.path[:0] = [os.path.join(ROOT, "src"), HERE]
    import numpy as np
    import jax
    import compare
    import devtrace
    import world
    from jax.flatten_util import ravel_pytree
    from reference import CohortReference, DenseReference

    devs, err = check(cell["chips"])
    if err:
        return fail(err)
    peaks = world.load_json("peaks.json")
    kind = devs[0].device_kind
    if kind not in peaks:
        return fail(f"no peak table entry for device kind {kind!r}")
    peak = peaks[kind]
    enable_compile_cache()
    counter = CompileCounter()
    cfg = world.load_json("configs", cell["config"] + ".json")
    traffic = world.load_json("traffic", cell["traffic"] + ".json")
    limits = world.load_json("limits", cell["name"] + ".json")
    model = world.model_module(cfg)
    n = traffic["periods_per_advance"]
    # the configuration's matmul precision, where it states one
    jax.config.update("jax_default_matmul_precision",
                      cfg.get("matmul_precision"))

    # ---- set-up: inputs, driver, warm-up through advance(n) -------------
    w = world.build(cfg, traffic, args.seed, devs)
    driver = w.driver
    progress(t_start, "inputs and driver built")
    prog_rows = advance(driver, n)
    progress(t_start, "first advance done")
    _, unravel = ravel_pytree(w.w0)
    prog_w = leaves_f64(unravel(driver.global_vec))
    warm = 1
    while True:
        before = counter.n
        advance(driver, n)
        warm += 1
        if counter.n == before:
            break
        if warm > 4:
            return fail("advance(n) still compiles after four calls")
    setup_s = time.perf_counter() - t_start
    progress(t_start, f"set-up done after {warm} calls")

    # ---- the window ------------------------------------------------------
    before = counter.n
    restarts = periods = 0
    trace_dir = None
    seconds = min(args.seconds, TRACE_SECONDS) if args.trace else args.seconds
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(trace_dir)
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench_window"):
        while True:
            with jax.profiler.TraceAnnotation("bench_advance"):
                rows = advance(driver, n)
            periods += n
            restarts += sum(r["n_participants"] for r in rows)
            if time.perf_counter() - t0 >= seconds:
                break
    window_s = time.perf_counter() - t0
    if args.trace:
        jax.profiler.stop_trace()
    if counter.n != before:
        return fail(f"{counter.n - before} compilations inside the window")
    progress(t_start, f"window closed: {periods} periods")
    peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in devs)
    final_finite = bool(np.all(np.isfinite(driver.global_vec)))

    metrics, device = {}, {"platform": devs[0].platform, "kind": kind,
                           "count": len(devs),
                           "memory_peak_bytes": int(peak_bytes)}
    breakdown = None
    if args.trace:
        tr = devtrace.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = devtrace.busy_s(tr)
        device["window_s"] = tr.window_s
        breakdown = devtrace.breakdown(tr)
        ctx = SimpleNamespace(
            trace=tr, periods=periods, restarts=restarts, chips=len(devs),
            peak=peak, traffic=traffic, cfg=cfg,
            shapes=shapes_of(driver, traffic, w.w0, len(devs)),
            step_flops=model.step_flops(cfg, traffic),
            train_ms=(train_span_ms(driver, traffic)
                      if any(m["name"] == "train_ms" for m in per_layer)
                      else None))
        for m in per_layer:
            v = load_metric(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        values = {"period_ms": 1e3 * window_s / periods, "setup_s": setup_s}
        for m in end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}

    # ---- correctness, with the program's state freed --------------------
    del driver, w.driver
    gc.collect()
    jax.config.update("jax_default_matmul_precision", None)
    cls = CohortReference if traffic.get("cohort_size") else DenseReference
    ref = cls(model, cfg, traffic, w.fed, w.w0, w.seeds.fl)
    ref_rows, ref_w = ref.run(n)
    del ref
    nums, info = compare.numbers(prog_rows, ref_rows, prog_w,
                                 leaves_f64(ref_w), leaves_f64(w.w0))
    correct, checks = compare.verdict(nums, limits)
    progress(t_start, "reference done")
    print(f"window: {periods} periods in {window_s:.3f} s, set-up "
          f"{setup_s:.3f} s, warm-up calls {warm}; compared "
          f"{info['uploads']} uploads over {n} periods, "
          f"{info['leaves_left_out']} of {info['leaves']} leaves left out",
          file=sys.stderr)
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    out = {"correct": correct, "attempted": periods,
           "failed": 0 if final_finite else periods, "metrics": metrics,
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
