"""Production mesh builders.

Single pod: 16x16 = 256 chips, axes ("data", "model").
Multi-pod:  2x16x16 = 512 chips, axes ("pod", "data", "model").

FL-mode client placement (DESIGN.md §4): the PAOTA client axis is
("data",) — or ("pod","data") multi-pod — for architectures whose full
replica fits one model-parallel group; for the giant MoE archs the client
axis is ("pod",) (2 semi-async cohorts) with expert-parallel sharding over
"data" inside each client.

Functions, not module constants: importing this module never touches jax
device state (required so smoke tests see 1 CPU device while the dry-run
sees 512 forced host devices).
"""
from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with automatic (GSPMD) axes. The round core and
    the model code place arrays with ``PartitionSpec``s and let the
    partitioner propagate the rest; ``jax.make_mesh`` defaults to
    Explicit axes, under which sharding becomes part of every array's
    type and that code no longer type-checks."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_cpu_mesh(*, data: int = 1, model: int = 1):
    """Tiny mesh over real local devices (tests on CPU).

    On a CPU-only host extra devices can be forced BEFORE jax initializes
    with ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (the test
    suite's conftest does this; ``benchmarks.sharded_round_bench``
    re-execs itself with it set). Once jax has initialized, the flag is
    inert — hence the hard error here rather than a silent 1-device mesh.
    """
    n = data * model
    if len(jax.devices()) < n:
        raise RuntimeError(
            f"need {n} devices, have {len(jax.devices())}; on CPU force "
            f"virtual devices with XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n} before jax "
            f"initializes (set it in the environment, not after import)")
    return make_mesh((data, model), ("data", "model"))


def make_pod_mesh(*, pods: int = 2, data: int = 256, tp: int = 1):
    """("pod", "data"[, "tp"]) client mesh: ``pods`` semi-async
    aggregation groups of ``data`` client shards each. ``tp > 1`` appends
    an intra-client tensor-parallel axis — every client replica's model
    storage spans ``tp`` chips (``ShardedPAOTA`` TP-shards the stacked
    payload leaves over it; see EXPERIMENTS.md §Intra-client TP).
    ``tp=1`` returns the historical two-axis ("pod", "data") mesh
    unchanged. Same forced-host-device contract as ``make_cpu_mesh``:
    on CPU set XLA_FLAGS before jax initializes."""
    n = pods * data * tp
    if len(jax.devices()) < n:
        raise RuntimeError(
            f"need {n} devices, have {len(jax.devices())}; on CPU force "
            f"virtual devices with XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n} before jax "
            f"initializes (set it in the environment, not after import)")
    if tp == 1:
        return make_mesh((pods, data), ("pod", "data"))
    return make_mesh((pods, data, tp), ("pod", "data", "tp"))


def make_client_mesh(shards: int | None = None):
    """All-devices 1-model-axis mesh (("data", "model") = (n, 1)) for the
    mesh-sharded PAOTA round: the whole device pool becomes the client
    axis (``data``), each client replica fitting a single device — the
    small-federation analogue of DESIGN.md §4's flattened-client layout."""
    n = shards if shards is not None else len(jax.devices())
    return make_cpu_mesh(data=n, model=1)


def data_axes(mesh) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def client_axes_for(cfg, mesh) -> Tuple[str, ...]:
    """PAOTA client axis selection (DESIGN.md §4 + EXPERIMENTS.md §Perf
    iter A):

    * giant MoE (llama4/mixtral): replica needs EP+TP inside -> client=pod
      (2 semi-async cohorts multi-pod; degenerate sync single-pod);
    * small archs whose attention heads do NOT divide the model axis
      (smollm 9H, internvl2 14H, minicpm 36H): TP sharding replicated
      their attention compute 16x — flatten clients over BOTH axes
      (one chip per client, 256/512 clients, zero TP collectives);
    * everything else: client=data groups with 16-way TP inside.
    """
    giant = cfg.name.startswith(("llama4", "mixtral"))
    if giant:
        return ("pod",) if "pod" in mesh.axis_names else ()
    msize = mesh.shape.get("model", 1)
    heads_bad = cfg.num_heads and cfg.num_heads % msize != 0
    # replica must fit one chip: params bf16 + grads + activations << 16GB
    small = cfg.name.startswith(("smollm", "internvl2", "minicpm"))
    if heads_bad and small:
        return data_axes(mesh) + ("model",)
    return data_axes(mesh)
