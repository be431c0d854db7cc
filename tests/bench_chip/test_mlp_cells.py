"""The paper-MLP cell's readers (``compress_ms``, ``gather_superpose_
roofline``, ``device_ops_per_period``) on traces and compiled text made up
by hand, and the cell as ``run.load_cell`` loads it."""
import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks", "chip")
sys.path.insert(0, BENCH)

import devtrace  # noqa: E402
import kernels  # noqa: E402
import run  # noqa: E402
import scopes  # noqa: E402

SCOPE = "jit(_run_scan)/while/body/closed_call"
HLO = f"""\
HloModule jit__run_scan, is_scheduled=true

ENTRY %main.1 (p.1: f32[4]) -> f32[4] {{
  %p.1 = f32[4]{{0}} parameter(0)
  %fusion.3 = f32[4]{{0}} fusion(%p.1), kind=kLoop, calls=%c, metadata={{op_name="{SCOPE}/paota.compress/floor"}}
  %sort.4 = f32[4]{{0}} sort(%p.1), metadata={{op_name="{SCOPE}/paota.compress/top_k"}}
  %gather_superpose_pallas.2 = (f32[1,8]{{1,0}}) custom-call(%p.1), custom_call_target="tpu_custom_call", metadata={{op_name="{SCOPE}/paota.superpose/jit(gather_superpose_pallas)/gather_superpose_pallas/pallas_call"}}
  %while.9 = (s32[]) while(%t), body=%b, metadata={{op_name="jit(_run_scan)/while"}}
  ROOT %fusion.7 = f32[4]{{0}} fusion(%p.1), kind=kLoop, calls=%c, metadata={{op_name="{SCOPE}/paota.carry_write/select_n"}}
}}
"""


def _ev(name, start, dur):
    return SimpleNamespace(name=name, start_ns=start, duration_ns=dur)


def _trace():
    """Two chips; the window spans [100, 1100) ns."""
    line = lambda name, evs: SimpleNamespace(name=name, events=evs)
    plane = lambda name, lines: SimpleNamespace(name=name, lines=lines)
    return devtrace.from_profile(SimpleNamespace(planes=[
        plane("/host:CPU", [line("python", [_ev("bench_window", 100, 1000)])]),
        plane("/device:TPU:0", [line("XLA Ops", [
            _ev("%fusion.3 = f32[4]{0} fusion(%p.1)", 50, 100),  # 50 in
            _ev("%while.9 = (s32[]) while(%t), body=%b", 150, 900),
            _ev("%sort.4 = f32[4]{0} sort(%p.1)", 200, 30),
            _ev("%gather_superpose_pallas.2 = (f32[1,8]) custom-call(%p.1)",
                300, 200),
            _ev("%fusion.7 = f32[4]{0} fusion(%p.1)", 600, 40),
            _ev("%fusion.3 = f32[4]{0} fusion(%p.1)", 700, 20),
            _ev("%fusion.7 = f32[4]{0} fusion(%p.1)", 1100, 50),  # after
        ])]),
        plane("/device:TPU:1", [line("XLA Ops", [
            _ev("%fusion.3 = f32[4]{0} fusion(%p.1)", 200, 60),
            _ev("%gather_superpose_pallas.2 = (f32[1,8]) custom-call(%p.1)",
                300, 100),
            _ev("%sort.4 = f32[4]{0} sort(%p.1)", 500, 30),
        ])]),
    ]))


def _ctx(smap, periods=2, **kw):
    return SimpleNamespace(trace=_trace(), scopes=smap, periods=periods,
                           traffic={"periods_per_advance": periods}, **kw)


def test_compress_ms_reads_its_own_scope_per_period():
    ctx = _ctx(scopes.scope_map(HLO))
    # chip 0: fusion.3 [100, 150) + [700, 720), sort.4 30 ns = 100 ns;
    # chip 1: fusion.3 60 + sort.4 30 = 90 ns; mean 95 ns over 2 periods
    assert run.load_metric("compress_ms")(ctx) == pytest.approx(95e-9 * 1e3
                                                                 / 2)
    # the carry writes beside it count none of those operations: chip 0's
    # fusion.7 inside the window, 40 ns, over two chips and two periods
    assert run.load_metric("carry_write_ms")(ctx) == pytest.approx(
        40e-9 / 2 * 1e3 / 2)


def test_compress_ms_says_nothing_where_the_program_has_no_such_scope():
    """A program whose round has stage scopes but no compression stage
    (the dense step, or a program older than the scope) reads nothing,
    and does not raise; the other stage readers still read."""
    ctx = _ctx(scopes.scope_map(HLO.replace("paota.compress",
                                            "paota.carry_write")))
    assert run.load_metric("compress_ms")(ctx) is None
    assert run.load_metric("carry_write_ms")(ctx) > 0
    assert run.load_metric("compress_ms")(_ctx({})) is None


def test_gather_superpose_roofline_by_hand():
    sh = {"rows": 64, "leaves": [8070], "s": 504, "slot_bytes": 1,
          "delta_bytes": 4, "payload_bytes": 0}
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ctx = _ctx({}, shapes=sh, peak=peak)
    # flops 2 m s + 2 d; bytes m s (1 + 4) + 4 m + 8 d: memory-bound
    flops, bytes_ = 2 * 64 * 504 + 2 * 8070, 64 * 504 * 5 + 4 * 64 + 8 * 8070
    assert kernels.gather_superpose(sh) == (flops, bytes_)
    # the slower chip's kernel time, 200 ns, over 2 periods
    want = 100.0 * (bytes_ / 819e9) / (200e-9 / 2)
    assert run.load_metric("gather_superpose_roofline")(ctx) == \
        pytest.approx(want)
    ctx.trace.devices = {k: [e for e in v if "gather" not in e[0]]
                         for k, v in ctx.trace.devices.items()}
    assert run.load_metric("gather_superpose_roofline")(ctx) is None


def test_device_ops_per_period_counts_the_window():
    # chip 0: fusion.3 (clipped in), sort.4, the kernel, fusion.7,
    # fusion.3 = 5 (the while and the fusion.7 after the window left out);
    # chip 1: 3; mean 4 over 2 periods
    assert run.load_metric("device_ops_per_period")(_ctx({})) == 2.0
    ctx = _ctx({}, periods=4)
    ctx.trace.devices = {}
    assert run.load_metric("device_ops_per_period")(ctx) is None


CELLS = {
    "paper-mlp.k1000-cohort64-rm16-int8": [
        "gather_superpose_roofline", "compress_ms", "device_ops_per_period"],
}


@pytest.mark.parametrize("name", list(CELLS))
def test_mlp_cells_load_with_their_metrics(name):
    import world
    cell, end_to_end, per_layer = run.load_cell(name)
    assert cell["config"] == "paper-mlp" and cell["chips"] == 1
    assert [m["name"] for m in end_to_end] == ["period_ms", "setup_s"]
    assert [m["name"] for m in per_layer] == CELLS[name]
    assert all(m["moves"] == "period_ms" for m in per_layer)
    cfg = world.load_json("configs", "paper-mlp.json")
    assert cfg["matmul_precision"] == "highest"
    traffic = world.load_json("traffic", cell["traffic"] + ".json")
    assert traffic["params_mode"] == "raveled"
    assert bool(traffic.get("compress")) == ("compress_ms" in CELLS[name])
