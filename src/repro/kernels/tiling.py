"""How the round kernels block a client-stacked leaf.

The round kernels (``round_stats_pallas``, ``superpose_normalize_pallas``)
stream each (K, ...) leaf of the round's planes from HBM once, in one of
two views:

* native — a leaf of rank >= 3, (K, *lead, S, C), is read as (K, L, S, C)
  with L = prod(lead). Merging major dims leaves the minor (S, C) pair,
  and with it the TPU's tiled layout, as it is: the view is a bitcast, not
  a relayout. Blocks are (K, br, C) rows of one l; the grid runs over
  (L, cdiv(S, br)).
* stripes — a rank-2 leaf (K, n) (a raveled model, per-client vectors,
  cohort slot rows) is read in (K, block_d) lane stripes; the grid runs
  over cdiv(n, block_d).

The compressed cohort's scatter (``gather_superpose_pallas``) blocks
three axes instead (``scatter_blocks``): the (H, 128) aggregate in
stripes of rows, and the (m, s) slot plane in blocks of rows and lanes.

Block sizes come from bytes, not a constant: about ``PLANE_BLOCK_BYTES``
per block of one (K, ...) plane, and every operand, double-buffered, in
``VMEM_BYTES``. A grid step costs a fixed fraction of a microsecond, so a
block has to carry enough bytes to hide it. A ragged last block is masked
inside the kernel (rows or lanes past the leaf's end are read as
garbage), never padded in HBM. Where not one row tile of a native block
fits, the leaf is read as stripes of its flattened (K, n) view.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
# one block of one (K, ...) plane: ~1.6 us of HBM time on a v5e, against
# a fixed cost of a fraction of a microsecond per grid step
PLANE_BLOCK_BYTES = 2 << 20
# every operand's block, double-buffered; Mosaic's scoped VMEM limit on a
# v5e is 16 MiB, and the kernel bodies keep their temporaries in vregs
VMEM_BYTES = 12 << 20
# the one-hot operands of one slot row in ``gather_superpose_pallas``:
# the (hb, bs) row one-hot and the (128, bs) lane one-hot, f32
SCATTER_BLOCK_BYTES = 1 << 20


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def sublanes(dtype) -> int:
    """Rows of one (sublane x 128) VMEM tile: 8 for f32, 16 for bf16."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


@dataclass(frozen=True)
class Blocks:
    """A blocked axis: ``count`` blocks of ``size`` (rows or lanes), the
    last holding ``tail`` valid ones."""
    size: int
    count: int
    tail: int


def _split(extent: int, most: int, align: int) -> Blocks:
    """The fewest blocks of at most ``most`` covering ``extent``, sized
    evenly and rounded up to ``align``: the last block is as full as the
    alignment lets it be."""
    count = -(-extent // most)
    size = _round_up(-(-extent // count), align)
    count = -(-extent // size)
    return Blocks(size, count, extent - (count - 1) * size)


def native_view(shape) -> tuple:
    """(K, L, S, C) of a rank >= 3 stacked leaf (K, *lead, S, C)."""
    return (shape[0], math.prod(shape[1:-2]), shape[-2], shape[-1])


def native_rows(k: int, s: int, c: int, planes, vectors,
                plane_bytes: int = PLANE_BLOCK_BYTES) -> Blocks | None:
    """Rows br of a native (K, br, C) block, or None where one row tile
    does not fit. ``planes``: dtypes of the (K, S, C) operands; ``vectors``:
    dtypes of the (S, C) ones (direction, noise, aggregate). br is a
    multiple of the operands' largest row tile; a leaf with fewer rows
    than that is one block, read past its end."""
    cp = _round_up(c, LANES)
    tile = max(sublanes(d) for d in (*planes, *vectors))
    plane_row = k * cp * max(jnp.dtype(d).itemsize for d in planes)
    all_rows = (sum(k * cp * jnp.dtype(d).itemsize for d in planes)
                + sum(cp * jnp.dtype(d).itemsize for d in vectors))
    most = min(plane_bytes // plane_row, VMEM_BYTES // (2 * all_rows))
    most -= most % tile
    if most < tile:
        return None
    return _split(s, most, tile)


def stripe_lanes(k: int, n: int, planes, vectors,
                 plane_bytes: int = PLANE_BLOCK_BYTES,
                 weight_rows: int = 0) -> Blocks:
    """Lanes block_d of a (K, block_d) stripe: a multiple of 128, never
    below 128 (so at large K no wider than the plane budget allows), or
    the whole n where n fits in one block. (1, n) vectors pad to a row
    tile in VMEM. ``weight_rows``: (1, K) f32 rows resident across the
    grid (the superposition's powers and mask), counted before the
    stripes."""
    col = [_round_up(k, sublanes(d)) * jnp.dtype(d).itemsize for d in planes]
    all_cols = sum(col) + sum(sublanes(d) * jnp.dtype(d).itemsize
                              for d in vectors)
    weights = 2 * weight_rows * sublanes(jnp.float32) * _round_up(k, LANES) * 4
    most = min(plane_bytes // max(col),
               (VMEM_BYTES - weights) // (2 * all_cols))
    most = max(LANES, most - most % LANES)
    if n <= most:
        return Blocks(n, 1, n)
    return _split(n, most, LANES)


@dataclass(frozen=True)
class ScatterBlocks:
    """How ``gather_superpose_pallas`` blocks its axes: ``tiles``, rows of
    one (hb, 128) stripe of the (H, 128) aggregate; ``rows`` and
    ``lanes``, one (bm, bs) block of the (m, s) slot plane."""
    tiles: Blocks
    rows: Blocks
    lanes: Blocks


def _whole_or_split(extent: int, most: int, align: int) -> Blocks:
    return Blocks(extent, 1, extent) if extent <= most else _split(
        extent, most, align)


def scatter_blocks(m: int, s: int, h: int, value_dtype,
                   onehot_bytes: int = SCATTER_BLOCK_BYTES) -> ScatterBlocks:
    """Blocks of the compressed scatter from bytes: the lane one-hot
    (128, bs) takes at most half of ``onehot_bytes`` and the row one-hot
    (hb, bs) the rest, so a slot row's one-hot work stays bounded for any
    d; the slot block's values and indices, double-buffered, and its
    values widened to f32 take at most ``onehot_bytes`` too. Each axis is
    whole where it fits, else split into aligned blocks (a multiple of
    128 lanes, of 8 aggregate rows, of the values' row tile)."""
    most = max(LANES, onehot_bytes // (2 * LANES * 4))
    lanes = _whole_or_split(s, most - most % LANES, LANES)
    bs = _round_up(lanes.size, LANES)
    most = max(8, onehot_bytes // (4 * bs) - LANES)
    tiles = _whole_or_split(h, most - most % 8, 8)
    tile = max(sublanes(value_dtype), sublanes(jnp.int32))
    most = max(tile, onehot_bytes // (
        bs * (2 * (jnp.dtype(value_dtype).itemsize + 4) + 4)))
    rows = _whole_or_split(m, most - most % tile, tile)
    return ScatterBlocks(tiles, rows, lanes)


def per_block(blocks: Blocks, i, body):
    """``body(valid)`` on grid step ``i`` of a blocked axis, with the
    static count of valid rows or lanes: ``blocks.size`` on every block
    but a ragged last one, ``blocks.tail`` there."""
    if blocks.tail == blocks.size:
        body(blocks.size)
        return
    pl.when(i < blocks.count - 1)(lambda: body(blocks.size))
    pl.when(i == blocks.count - 1)(lambda: body(blocks.tail))
