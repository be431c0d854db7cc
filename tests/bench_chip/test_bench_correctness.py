"""The comparison that decides ``correct``, on the CPU at sizes a test run
holds: the program agrees with the plain reference; the control (the
reference one precision lower, in the program's place) fails; and a whole
run with the timed path broken underneath reports ``correct: false``."""
import json
import os
import sys

import jax
import jax.flatten_util
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks", "chip")
sys.path.insert(0, BENCH)

import compare  # noqa: E402
import run  # noqa: E402
import world  # noqa: E402
from reference import CohortReference, DenseReference  # noqa: E402

TINY_LLAMA = {"model": "llama", "hidden_size": 64, "intermediate_size": 128,
              "num_hidden_layers": 2, "num_attention_heads": 4,
              "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 512,
              "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
              "tie_word_embeddings": True}


def _small_k4(load=None):
    k4 = (load or world.load_json)("traffic", "k4.json")
    return dict(k4, clients=3, data=dict(k4["data"], sequences=6, seq_len=16))


def _cases():
    mlp = world.load_json("configs", "paper-mlp.json")
    k100 = dict(world.load_json("traffic", "k100.json"), clients=24)
    cohort = world.load_json("traffic", "k1000-cohort64-rm16-int8.json")
    cohort = dict(cohort, clients=120, cohort_size=24)
    return {"paper-mlp": (mlp, k100, None, 1),
            "llama": (TINY_LLAMA, _small_k4(), "smollm-135m.k4", 1),
            "cohort": (mlp, cohort, None, 1),
            "sharded": (mlp, dict(k100, driver="sharded"), None, 4)}


def _program(cfg, traffic, seed, chips=1):
    w = world.build(cfg, traffic, seed, jax.devices()[:chips])
    n = traffic["periods_per_advance"]
    rows = w.driver.advance(n)
    _, unravel = jax.flatten_util.ravel_pytree(w.w0)
    leaves = [np.asarray(a, np.float64) for a in jax.tree_util.tree_leaves(
        unravel(jnp.asarray(w.driver.global_vec)))]
    return w, rows, leaves


def _leaves(tree):
    return [np.asarray(a, np.float64) for a in jax.tree_util.tree_leaves(tree)]


@pytest.mark.parametrize("case", ["paper-mlp", "llama", "cohort", "sharded"])
def test_program_matches_reference_and_control_fails(case):
    """The llama case holds the smollm cell's limits; the MLP cases, at
    sizes the CPU holds, show the program within 1e-3 of the reference and
    the control an order of magnitude beyond it on one number at least.
    The sharded case drives the client mesh over four devices."""
    cfg, traffic, cell, chips = _cases()[case]
    w, rows, prog = _program(cfg, traffic, seed=2 ** 31 + 7, chips=chips)
    model = world.model_module(cfg)
    n = traffic["periods_per_advance"]
    w0 = _leaves(w.w0)
    cls = CohortReference if traffic.get("cohort_size") else DenseReference
    ref_rows, ref_w = cls(model, cfg, traffic, w.fed, w.w0, w.seeds.fl).run(n)
    nums, info = compare.numbers(rows, ref_rows, prog, _leaves(ref_w), w0)
    assert info["uploads"] > 0 and nums["uploads_mismatch"] == 0
    ctl_rows, ctl_w = cls(model, cfg, traffic, w.fed, w.w0, w.seeds.fl,
                          lower=True).run(n)
    ctl, _ = compare.numbers(ctl_rows, ref_rows, _leaves(ctl_w),
                             _leaves(ref_w), w0)
    if cell is not None:
        limits = world.load_json("limits", cell + ".json")
        assert compare.verdict(nums, limits)[0], nums
        assert not compare.verdict(ctl, limits)[0], ctl
    else:
        assert nums["global_diff"] < 1e-3, nums
        assert any(ctl[k] > 10 * max(nums[k], 1e-12)
                   for k in ("varsigma_gap", "change_gap", "global_diff")), ctl


# ---- a whole run with the timed path broken ----------------------------------

def _on_cpu(chips):
    return jax.devices()[:chips], None


@pytest.fixture
def cpu_run(monkeypatch, capsys):
    """``run.main`` on the smollm cell past the look for a chip, at a size
    a test holds: the configuration's widths and the traffic's sizes are
    cut, the cell's limits are the committed ones, and the peak table has
    an entry for this CPU. Returns the parsed result line."""
    load = world.load_json

    def load_json(*parts):
        if parts == ("configs", "smollm-135m.json"):
            return dict(TINY_LLAMA)
        if parts == ("traffic", "k4.json"):
            return _small_k4(load)
        d = load(*parts)
        if parts == ("peaks.json",):
            d[jax.devices()[0].device_kind] = d["TPU v5 lite"]
        return d
    monkeypatch.setattr(world, "load_json", load_json)

    def go():
        rc = run.main(["--workload", "smollm-135m.k4", "--seed", "4000000001",
                       "--seconds", "0.1", "--trace", "0"], check=_on_cpu)
        assert rc == 0
        return json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    return go


def _state_unchanged(monkeypatch):
    import repro.fl.fused as fused
    scan = fused.scan_rounds

    def stuck(carry, *a, **kw):
        _, outs = scan(carry, *a, **kw)
        return carry, outs
    monkeypatch.setattr(fused, "scan_rounds", stuck)


def _half_batch(monkeypatch):
    import models.llama as llama
    full = llama.program_loss

    def program_loss(cfg):
        loss = full(cfg)

        def half(params, batch):
            return loss(params, {"x": batch["x"][:batch["x"].shape[0] // 2]})
        return half
    monkeypatch.setattr(llama, "program_loss", program_loss)


def _answer_altered(monkeypatch):
    import repro.kernels.ops as ops
    superpose = ops.superpose_normalize

    def altered(*a, **kw):
        agg, vs = superpose(*a, **kw)
        return agg.at[jnp.argmax(jnp.abs(agg))].set(0.0), vs
    monkeypatch.setattr(ops, "superpose_normalize", altered)


@pytest.mark.parametrize("fault", [None, _state_unchanged, _half_batch,
                                   _answer_altered],
                         ids=["sound", "state_unchanged", "half_batch",
                              "answer_altered"])
def test_run_reports_the_broken_path(fault, cpu_run, monkeypatch):
    if fault is not None:
        fault(monkeypatch)
    out = cpu_run()
    assert out["correct"] is (fault is None), out["checks"]
    assert list(out)[-1] == "checks"
