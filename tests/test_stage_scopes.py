"""The round's stage scopes and the driver's host spans.

Every stage of the round runs under one of ``runtime.STAGE_SCOPES``, and
the scope reaches the compiled scan as ``op_name`` metadata, in the dense
and the cohort step alike. ``advance`` marks its host work with
``paota.advance`` around ``paota.dispatch``, ``paota.fetch`` and
``paota.rows`` in the profiler's trace.
"""
import functools
import glob
import os
import re

import jax
import pytest

from repro.core import ChannelConfig, SchedulerConfig
from repro.data.partition import partition_noniid
from repro.data.pipeline import build_federation
from repro.data.synthetic import make_mnist_like
from repro.fl import FLClient, FusedPAOTA, PAOTAConfig
from repro.fl.runtime import STAGE_SCOPES
from repro.models.mlp import init_mlp_params, mlp_loss

K = 8
OP_NAME = re.compile(r'op_name="([^"]*)"')


@functools.lru_cache(maxsize=1)
def _world():
    x, y, _, _ = make_mnist_like(n_train=400, n_test=10)
    return x, y, partition_noniid(y, n_clients=K, seed=0)


def _fused(transmit="model", **kw):
    x, y, parts = _world()
    clients = [FLClient(d, mlp_loss, batch_size=16, lr=0.1, local_steps=2)
               for d in build_federation(x, y, parts)]
    return FusedPAOTA(init_mlp_params(jax.random.PRNGKey(0)), clients,
                      ChannelConfig(), SchedulerConfig(n_clients=K, seed=1),
                      PAOTAConfig(transmit=transmit), **kw)


DRIVERS = {
    "raveled": {},
    "pytree_bf16": dict(params_mode="pytree", pending_dtype="bfloat16"),
    "cohort": dict(cohort_size=4),
    "cohort_randmask_int8": dict(transmit="delta", cohort_size=4,
                                 compress="randmask", compress_ratio=1 / 16,
                                 slot_dtype="int8"),
}


@pytest.mark.parametrize("driver", list(DRIVERS))
def test_every_stage_scope_reaches_the_compiled_scan(driver):
    names = OP_NAME.findall(_fused(**DRIVERS[driver]).compiled_scan_hlo(2))
    compressed = bool(DRIVERS[driver].get("compress"))
    for scope in STAGE_SCOPES:
        # only a compressed cohort has a compression stage
        want = compressed or scope != "paota.compress"
        assert any(scope in n for n in names) == want, scope
    # the stages do not nest in one another
    assert not any(sum(s in n for s in STAGE_SCOPES) > 1 for n in names)
    if compressed:
        # the stochastic rounding and the residual's top-k are compression
        for op in ("floor", "top_k"):
            assert any(n.endswith(f"/paota.compress/{op}") for n in names), op


def _events(trace_dir):
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            out += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events]
    return out


def test_advance_host_spans_nest(tmp_path):
    srv = _fused()
    srv.advance(2)
    jax.profiler.start_trace(str(tmp_path))
    srv.advance(2)
    jax.profiler.stop_trace()
    ev = _events(str(tmp_path))
    spans = lambda name: [(s, e) for n, s, e in ev if n == name]
    (a0, a1), = spans("paota.advance")
    inner = [spans(n) for n in ("paota.dispatch", "paota.fetch",
                                "paota.rows")]
    assert all(len(s) == 1 for s in inner), inner
    (d, f, r) = (s[0] for s in inner)
    assert a0 <= d[0] <= d[1] <= f[0] <= f[1] <= r[0] <= r[1] <= a1
