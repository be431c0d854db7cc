"""The benchmark's yardstick on the CPU: trace reduction, operation and
byte counts, the cell files, and the refusal to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks", "chip")
sys.path.insert(0, BENCH)

import devtrace  # noqa: E402
import kernels  # noqa: E402
import world  # noqa: E402


# ---- trace reduction --------------------------------------------------------

def _ev(name, start, dur):
    return SimpleNamespace(name=name, start_ns=start, duration_ns=dur)


def _profile():
    """Two chips and a host thread; the window spans [100, 1100) ns."""
    line = lambda name, evs: SimpleNamespace(name=name, events=evs)
    plane = lambda name, lines: SimpleNamespace(name=name, lines=lines)
    return SimpleNamespace(planes=[
        plane("/host:CPU", [line("python", [
            _ev("bench_window", 100, 1000), _ev("bench_advance", 100, 500),
            _ev("dispatch", 600, 100)])]),
        plane("/device:TPU:0", [
            line("XLA Modules", [_ev("jit_run_scan", 0, 2000)]),
            line("XLA Ops", [
                _ev("%fusion.1 = f32[4]{0} fusion(f32[4]{0} %p)", 50, 150),
                _ev("%while.9 = (s32[]) while((s32[]) %t), body=%b", 300, 150),
                _ev("%round_stats_pallas.3 = (f32[4,3]{1,0}) custom-call(%a)",
                    300, 100),
                _ev("%round_stats_pallas.4 = (f32[4,3]{1,0}) custom-call(%a)",
                    350, 100),                                  # overlap
                _ev("%superpose_normalize_pallas.1 = (f32[1,8]) "
                    "custom-call(%b)", 800, 100),
                _ev("%fusion.2 = f32[4]{0} fusion(f32[4]{0} %q)", 1050,
                    200)])]),                                   # ..1100
        plane("/device:TPU:1", [
            line("XLA Ops", [_ev("all-reduce.7", 200, 400)])]),
        plane("/device:TPU_NON_CORE:0", [
            line("XLA Ops", [_ev("other", 100, 1000)])]),
    ])


def test_trace_busy_union_and_idle_share():
    tr = devtrace.from_profile(_profile())
    assert tr.window == (100, 1100)
    assert sorted(tr.devices) == ["/device:TPU:0", "/device:TPU:1"]
    # chip 0: [100,200) + [300,450) + [800,900) + [1050,1100) = 400 ns;
    # chip 1: [200,600) = 400 ns
    assert devtrace.busy_s(tr) == pytest.approx(400e-9)
    gaps = devtrace.idle_gaps(tr.devices["/device:TPU:0"], *tr.window)
    assert gaps == [(200, 300), (450, 800), (900, 1050)]


def test_trace_kernel_time_by_name_and_breakdown():
    tr = devtrace.from_profile(_profile())
    assert devtrace.op_seconds(tr, r"round_stats_pallas") == [
        pytest.approx(200e-9)]
    assert devtrace.op_seconds(tr, r"all-reduce") == [pytest.approx(400e-9)]
    assert devtrace.op_seconds(tr, r"gather_superpose_pallas") == []
    bd = devtrace.breakdown(tr)
    # per kind, over two chips; the while that holds the kernels is left out
    assert dict((n, v) for n, v in bd["device_ops"]) == {
        "all-reduce": pytest.approx(200e-9),
        "round_stats_pallas": pytest.approx(100e-9),
        "fusion": pytest.approx(75e-9),
        "superpose_normalize_pallas": pytest.approx(50e-9)}
    # the longest idle gap of chip 0, [450, 800), sits inside the host's
    # bench_advance span at 625 ns: the innermost host event there
    assert bd["idle_gaps"][0] == ["dispatch", pytest.approx(350e-9)]


def test_trace_without_window_span_is_refused():
    prof = _profile()
    prof.planes[0].lines[0].events.pop(0)
    with pytest.raises(RuntimeError, match="bench_window"):
        devtrace.from_profile(prof)


# ---- operation and byte counts ----------------------------------------------

def test_kernel_counts_by_hand():
    sh = {"rows": 4, "leaves": [10, 6], "delta_bytes": 2, "payload_bytes": 2}
    # 3 columns x 2 flops x 4 rows x 16 + 2 x 16 for ||g||^2
    assert kernels.round_stats(sh) == (3 * 2 * 4 * 16 + 2 * 16,
                                       4 * 16 * 4 + 4 * 16)
    assert kernels.superpose(sh) == (2 * 4 * 16 + 2 * 16,
                                     4 * 16 * 2 + 8 * 16)
    delta = dict(sh, payload_bytes=0)
    assert kernels.round_stats(delta) == (2 * 2 * 4 * 16 + 2 * 16,
                                          4 * 16 * 2 + 4 * 16)
    comp = {"rows": 3, "leaves": [64], "s": 4, "slot_bytes": 1}
    assert kernels.gather_superpose(comp) == (2 * 3 * 4 + 2 * 64,
                                              3 * 4 * 5 + 4 * 3 + 8 * 64)
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert kernels.roofline_share(50.0, 20.0, 4.0, peak) == 50.0


def test_mlp_step_flops_by_hand():
    cfg = world.load_json("configs", "paper-mlp.json")
    tr = world.load_json("traffic", "k100.json")
    n_params = 784 * 10 + 10 + 10 * 10 + 10 + 10 * 10 + 10
    assert n_params == 8070
    assert world.model_module(cfg).step_flops(cfg, tr) == 6 * n_params * 32


def test_llama_step_flops_by_hand():
    import models.llama as llama
    cfg = {"hidden_size": 8, "num_attention_heads": 2,
           "num_key_value_heads": 1, "head_dim": 4, "intermediate_size": 16,
           "num_hidden_layers": 3, "vocab_size": 32}
    tr = {"batch": 2, "data": {"seq_len": 5}}
    per_layer = 8 * 8 + 2 * 8 * 4 + 8 * 8 + 3 * 8 * 16 + 2 * 8
    n = 32 * 8 + 8 + 3 * per_layer
    per_token = 6 * n + 12 * 3 * 5 * 2 * 4
    assert llama.step_flops(cfg, tr) == per_token * 5 * 2
    shapes = llama.shapes(cfg)
    assert sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
        shapes, is_leaf=lambda s: isinstance(s, tuple))) == n


def test_llama_layout_is_the_programs():
    """The benchmark's weights have the program's parameter tree."""
    import models.llama as llama
    from repro.models.config import ModelConfig
    from repro.models.transformer import init_model
    cfg = world.load_json("configs", "smollm-135m.json")
    mc = ModelConfig(name="t", family="dense",
                     num_layers=cfg["num_hidden_layers"],
                     d_model=cfg["hidden_size"],
                     num_heads=cfg["num_attention_heads"],
                     num_kv_heads=cfg["num_key_value_heads"],
                     head_dim=cfg["head_dim"], d_ff=cfg["intermediate_size"],
                     vocab_size=cfg["vocab_size"])
    prog = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), mc))
    ours = jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), cfg))
    assert (jax.tree_util.tree_structure(prog)
            == jax.tree_util.tree_structure(ours))
    assert ([a.shape for a in jax.tree_util.tree_leaves(prog)]
            == [a.shape for a in jax.tree_util.tree_leaves(ours)])


# ---- the cell files ---------------------------------------------------------

def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_cell_names_known_files_and_metrics():
    import compare
    b = _bench()
    configs = {c["name"]: c for c in b["configs"]}
    produced = {"period_ms", "setup_s"}
    for m in b["end_to_end"]:
        assert m["name"] in produced
    for m in b["per_layer"]:
        assert os.path.isfile(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py")), m["name"]
    for c in b["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        cfg = world.load_json("configs", c["name"] + ".json")
        assert world.model_module(cfg)
    for w in b["workloads"]:
        assert w["config"] in configs
        traffic = world.load_json("traffic", w["traffic"] + ".json")
        assert traffic["periods_per_advance"] >= 1
        limits = world.load_json("limits", w["name"] + ".json")
        assert set(limits) == set(compare.NAMES)
        e2e = [m["name"] for m in b["end_to_end"]
               if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(w["name"] in m.get("workloads", [w["name"]])
                   for m in b["per_layer"])


# ---- no TPU, no result --------------------------------------------------------

def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "smollm-135m.k4", "--seed", "5", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_without_tpu_exits_nonzero_with_no_result():
    res = _run(ROOT)
    assert res.returncode != 0
    assert "needs a TPU" in res.stderr
    assert res.stdout.strip() == ""


def test_run_with_only_the_benchmark_files_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in _bench()["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    res = _run(tmp_path)
    assert res.returncode != 0
    assert res.stdout.strip() == ""
