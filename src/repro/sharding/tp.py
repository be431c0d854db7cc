"""Intra-client tensor-parallel topology for the sharded PAOTA round.

The full production mesh is pods x clients x TP: the ("pod", "data")
axes shard the FEDERATION (each device group owns K_local clients) while
the "tp" axis shards each client's MODEL STORAGE — every stacked payload
leaf (pending / deltas, shape (K_local, ...)) keeps one trailing dim
split over the TP axis, so the per-device model-plane bytes drop ~1/TP.

Storage-parallel, compute-replicated: the globals stay replicated over
the TP axis and local training runs identically on every TP shard (full
leaves from the replicated global); only the carry WRITES slice the
trained leaves down to the shard's TP-local block. The round's tree
reductions then become TP-aware:

  * round stats (dots / norms) are computed on the TP-local blocks
    against a TP-sliced global direction and psum'd once over the TP
    axes (TP-replicated leaves — norms, biases, any non-dividing dim —
    are accumulated outside that psum so they count exactly once);
  * the AirComp superposition stays ONE model-sized psum: each TP shard
    embeds its local block at its position in the FULL flattened model
    vector (zeros elsewhere, TP-replicated leaves masked to the lead
    shard) and a single psum over clients x TP axes performs the
    cross-client sum and the TP gather simultaneously;
  * the AWGN draw is a function of the MODEL, not the layout: noise is
    drawn at the FULL leaf shapes from the replicated round key and
    added after that psum, so every TP extent consumes the same total
    noise and TP extent 1 is bit-identical to the flat program (the TP
    branches vanish at trace time when no topology is passed).

``TPTopology`` is a static (hashable) description threaded through
``paota_round_step`` exactly like the grouped ``GroupTopology``, and like
it reaches dense payload planes only (the sharded driver refuses a TP
mesh with a cohort); the sharded driver derives ``leaf_dims`` from the
computed pend_spec tree so slicing and GSPMD placement can never
disagree.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp


class TPTopology(NamedTuple):
    """Static intra-client TP description (trace-time constant).

    axes:      mesh axis names the model storage is sharded over.
    extents:   mesh extent of each axis (same order).
    shards:    product of extents (> 1 when the topology is active).
    leaf_dims: per tree_flatten leaf of the params tree, the UNSTACKED
               trailing-dim index sharded over the TP axes, or -1 for a
               TP-replicated leaf (no trailing dim divides).
    """
    axes: Tuple[str, ...]
    extents: Tuple[int, ...]
    shards: int
    leaf_dims: Tuple[int, ...]


def tp_linear_index(tp: TPTopology):
    """Row-major linear index of this device along the TP axes — matches
    GSPMD's split order when a dim is sharded over the axis tuple."""
    idx = jnp.int32(0)
    for a, n in zip(tp.axes, tp.extents):
        idx = idx * n + jax.lax.axis_index(a)
    return idx


def tp_slice(leaf, dim: int, tp: TPTopology):
    """This shard's TP-local block of a TP-replicated full leaf, along
    ``dim``. ``leaf.shape[dim]`` must be divisible by ``tp.shards`` (the
    spec builder guarantees it for every sharded leaf)."""
    size = leaf.shape[dim] // tp.shards
    return jax.lax.dynamic_slice_in_dim(
        leaf, tp_linear_index(tp) * size, size, axis=dim)


def tp_mask_lead(x, tp: TPTopology):
    """Zero ``x`` on every TP shard except linear index 0 — so a psum
    over the TP axes counts a TP-replicated partial exactly once (an
    exact sum of x and zeros, no 1/shards rounding)."""
    return jnp.where(tp_linear_index(tp) == 0, x, jnp.zeros_like(x))


def tp_full_structs(stacked_leaves, tp: TPTopology):
    """Full-model ShapeDtypeStructs for TP-local stacked leaves: each
    sharded leaf's TP dim (stacked position ``leaf_dims[i] + 1``) scaled
    back up by ``tp.shards``. Shape-only stand-ins for the noise draw and
    the finalize split — f32, matching the aggregation accumulator."""
    out = []
    for leaf, dim in zip(stacked_leaves, tp.leaf_dims):
        shape = list(leaf.shape)
        if dim >= 0:
            shape[dim + 1] *= tp.shards
        out.append(jax.ShapeDtypeStruct(tuple(shape), jnp.float32))
    return out
