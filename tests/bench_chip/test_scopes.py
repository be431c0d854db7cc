"""Device time per round stage and host time per driver span
(``benchmarks/chip/scopes.py`` and the readers built on it), on recorded
traces and compiled text made up by hand, and on a CPU trace of the
program itself."""
import collections
import functools
import glob
import os
import sys
from types import SimpleNamespace

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks", "chip")
sys.path.insert(0, BENCH)

import devtrace  # noqa: E402
import run  # noqa: E402
import scopes  # noqa: E402

STAGES = ("paota.schedule", "paota.stats", "paota.power", "paota.superpose",
          "paota.train", "paota.carry_write")
SCOPE_READERS = ("train_scan_ms", "carry_write_ms", "round_core_ms",
                 "unscoped_ms")

HLO = """\
HloModule jit__run_scan, is_scheduled=true

%fused_computation.3 (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %add.9 = f32[4]{0} add(%param_0, %param_0), metadata={op_name="jit(_run_scan)/while/body/closed_call/paota.stats/add" stack_frame_id=3}
}

ENTRY %main.1 (p.1: f32[4]) -> f32[4] {
  %p.1 = f32[4]{0} parameter(0)
  %fusion.1 = f32[4]{0} fusion(%p.1), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(_run_scan)/while/body/closed_call/paota.stats/add"}
  %round_stats_pallas.3 = (f32[4,3]{1,0}) custom-call(%p.1), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={}}, metadata={op_name="jit(_run_scan)/while/body/closed_call/paota.stats/cond/branch_0_fun/jit(round_stats_pallas)/round_stats_pallas/pallas_call" stack_frame_id=48}
  %copy.4 = f32[4]{0} copy(%p.1)
  %fusion.2 = f32[4]{0} fusion(%p.1), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(_run_scan)/while/body/closed_call/paota.train/jit(step)/paota.train_inner/dot_general"}
  %dynamic-update-slice.5 = f32[4]{0} dynamic-update-slice(%p.1), metadata={op_name="jit(_run_scan)/while/body/dynamic_update_slice"}
  %while.9 = (s32[]) while(%t), body=%b, metadata={op_name="jit(_run_scan)/while"}
  ROOT %fusion.7 = f32[4]{0} fusion(%p.1), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(_run_scan)/while/body/closed_call/paota.carry_write/select_n"}
}
"""


def test_scope_map_reads_each_instruction_op_name():
    smap = scopes.scope_map(HLO)
    assert smap["round_stats_pallas.3"].endswith(
        "/paota.stats/cond/branch_0_fun/jit(round_stats_pallas)/"
        "round_stats_pallas/pallas_call")     # past kernel_metadata={}
    assert smap["add.9"].endswith("/paota.stats/add")   # fused, ROOT
    assert smap["fusion.7"].endswith("/paota.carry_write/select_n")
    assert smap["copy.4"] == smap["p.1"] == ""            # no op_name
    assert "all-gather.8" not in smap


def _ev(name, start, dur):
    return SimpleNamespace(name=name, start_ns=start, duration_ns=dur)


def _trace(host=()):
    """One chip; the window spans [100, 1100) ns."""
    line = lambda name, evs: SimpleNamespace(name=name, events=evs)
    plane = lambda name, lines: SimpleNamespace(name=name, lines=lines)
    return devtrace.from_profile(SimpleNamespace(planes=[
        plane("/host:CPU", [line("python", [_ev("bench_window", 100, 1000)]
                                 + [_ev(*h) for h in host])]),
        plane("/device:TPU:0", [line("XLA Ops", [
            _ev("%fusion.1 = f32[4]{0} fusion(f32[4]{0} %p.1)", 50, 100),
            _ev("%while.9 = (s32[]) while((s32[]) %t), body=%b", 150, 900),
            _ev("%round_stats_pallas.3 = (f32[4,3]) custom-call(%p.1)",
                200, 100),
            _ev("%fusion.2 = f32[4]{0} fusion(f32[4]{0} %p.1)", 300, 200),
            _ev("%copy.4 = f32[4]{0} copy(f32[4]{0} %p.1)", 500, 40),
            _ev("%dynamic-update-slice.5 = f32[4]{0} dynamic-update-slice()",
                540, 60),
            _ev("%all-gather.8 = f32[8]{0} all-gather(%p.1)", 600, 30),
            _ev("%fusion.7 = f32[4]{0} fusion(f32[4]{0} %p.1)", 700, 50),
            _ev("%fusion.1 = f32[4]{0} fusion(f32[4]{0} %p.1)", 1080, 100),
        ])])]))


def test_scope_seconds_by_stage():
    tr, smap = _trace(), scopes.scope_map(HLO)
    sec = lambda scope: scopes.scope_seconds(tr, smap, scope)
    # fusion.1 clipped to the window: [100, 150) + [1080, 1100); the
    # kernel [200, 300); the while that spans them is left out
    assert sec("paota.stats") == [pytest.approx(170e-9)]
    # a nested scope matches its parent's name as a substring
    assert sec("paota.train") == [pytest.approx(200e-9)]
    assert sec("paota.train_inner") == [pytest.approx(200e-9)]
    assert sec("paota.carry_write") == [pytest.approx(50e-9)]
    # no paota. scope: copy.4 (no op_name), the scan's output stacking,
    # and all-gather.8, which the compiled text does not name (the one
    # unresolved operation)
    assert sec(None) == [pytest.approx(130e-9)]
    assert scopes.unresolved_seconds(tr, smap) == [pytest.approx(30e-9)]
    assert scopes.unscoped_kinds(tr, smap) == [
        ("dynamic-update-slice", pytest.approx(60e-9)),
        ("copy", pytest.approx(40e-9)), ("all-gather", pytest.approx(30e-9))]
    # the stages and the unscoped rest count every operation of the
    # window once: 50 + 20 + 100 + 200 + 40 + 60 + 30 + 50 ns
    assert (sum(sec(s)[0] for s in STAGES) + sec(None)[0]
            == pytest.approx(550e-9))


def test_host_span_seconds_inside_the_window():
    tr = _trace(host=[("paota.dispatch", 50, 100), ("paota.dispatch",
                                                     400, 30),
                      ("paota.rows", 1090, 20)])
    assert scopes.host_span_seconds(tr, "paota.dispatch") == \
        pytest.approx(80e-9)
    assert scopes.host_span_seconds(tr, "paota.rows") == pytest.approx(10e-9)
    assert scopes.host_span_seconds(tr, "paota.fetch") == 0.0


def _ctx(trace, smap, periods=2):
    return SimpleNamespace(trace=trace, scopes=smap, periods=periods,
                           traffic={"periods_per_advance": periods})


def test_stage_readers_per_period(capsys):
    ctx = _ctx(_trace(), scopes.scope_map(HLO))
    read = {m: run.load_metric(m)(ctx) for m in SCOPE_READERS}
    assert read == {"train_scan_ms": pytest.approx(1e-4),
                    "carry_write_ms": pytest.approx(2.5e-5),
                    "round_core_ms": pytest.approx(8.5e-5),
                    "unscoped_ms": pytest.approx(6.5e-5)}
    err = capsys.readouterr().err
    # all-gather.8: 30 of the 970 ns the chip is busy in the window (the
    # while that holds the body counts as busy)
    assert "unresolved operations 0.0000 ms in the window, 3.0928%" in err
    assert ("by kind: dynamic-update-slice 0.000, copy 0.000, all-gather "
            "0.000") in err


@pytest.mark.parametrize("driver", ["no_stage", "none"])
def test_stage_readers_say_nothing_without_stage_scopes(driver, monkeypatch):
    """A program that names no stage (the scopes' parent) gives an empty
    map, and so does a process with no one driver alive: every stage
    reader returns nothing, and none raises."""
    text = HLO.replace("paota.", "other.")
    found = (SimpleNamespace(compiled_scan_hlo=lambda n: text)
             if driver == "no_stage" else None)
    monkeypatch.setattr(scopes, "live_driver", lambda: found)
    ctx = _ctx(_trace(), None)
    for m in SCOPE_READERS:
        assert run.load_metric(m)(ctx) is None
    assert ctx.scopes == {}


def _advance_spans(rows_ns):
    """Two advance calls of [dispatch 30, fetch 400, rows] ns each."""
    out = []
    for t in (100, 600):
        out += [("paota.advance", t, 430 + rows_ns),
                ("paota.dispatch", t, 30),
                ("paota.fetch", t + 30, 400),
                ("paota.rows", t + 430, rows_ns)]
    return out


def test_driver_host_ms_reads_dispatch_and_rows():
    read = run.load_metric("driver_host_ms")
    ctx = _ctx(_trace(host=_advance_spans(10)), {}, periods=4)
    assert read(ctx) == pytest.approx(1e-6 * 2 * (30 + 10) / 4)
    # no paota.advance span: a program without the spans
    assert read(_ctx(_trace(), {})) is None


def test_driver_host_ms_refuses_spans_that_do_not_add_up(capsys):
    spans = _advance_spans(10)
    spans[0] = ("paota.advance", 100, 600)        # 160 ns nobody accounts for
    assert run.load_metric("driver_host_ms")(
        _ctx(_trace(host=spans), {})) is None
    assert "apart by more than 5%" in capsys.readouterr().err


# ---- the program's own names, on a CPU trace --------------------------------

@functools.lru_cache(maxsize=1)
def _small_driver():
    from repro.core import ChannelConfig, SchedulerConfig
    from repro.data.partition import partition_noniid
    from repro.data.pipeline import build_federation
    from repro.data.synthetic import make_mnist_like
    from repro.fl import FLClient, FusedPAOTA, PAOTAConfig
    from repro.models.mlp import init_mlp_params, mlp_loss
    x, y, _, _ = make_mnist_like(n_train=400, n_test=10)
    clients = [FLClient(d, mlp_loss, batch_size=16, lr=0.1, local_steps=2)
               for d in build_federation(x, y, partition_noniid(
                   y, n_clients=8, seed=0))]
    return FusedPAOTA(init_mlp_params(jax.random.PRNGKey(0)), clients,
                      ChannelConfig(), SchedulerConfig(n_clients=8, seed=1),
                      PAOTAConfig())


def test_cpu_trace_ops_resolve_to_every_stage(tmp_path, monkeypatch):
    """The operations a CPU trace names are instructions of the compiled
    scan's text, as ``stage_map`` takes it from the live driver, and they
    fall under every stage."""
    from jax.profiler import ProfileData
    srv = _small_driver()
    srv.advance(2)
    jax.profiler.start_trace(str(tmp_path))
    srv.advance(2)
    jax.profiler.stop_trace()
    monkeypatch.setattr(scopes, "live_driver", lambda: srv)
    smap = scopes.stage_map(_ctx(None, None))
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    seen = collections.Counter()
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                for s in STAGES:
                    seen[s] += s in smap.get(e.name, "")
    assert all(seen[s] > 0 for s in STAGES), seen
