"""Operations and bytes the round kernels need per period, from the
cell's shapes: what the algorithm must read, write and compute, not what
a kernel happens to do.

``shapes`` is the run record's ``shapes`` entry: ``rows`` (payload rows
per chip: K / chips dense, m for a cohort), ``leaves`` (the model's leaf
sizes), ``delta_bytes`` and ``payload_bytes`` (bytes per stored element;
``payload_bytes`` is 0 under delta transmit, where the payload is the
delta plane), and for a compressed cohort ``s`` and ``slot_bytes``.
"""
from __future__ import annotations

F32 = 4


def round_stats(sh) -> tuple:
    """eq.-25 stats sweep: per row, <delta, g>, ||delta||^2 and (model
    transmit) ||payload||^2 over every leaf, plus ||g||^2."""
    k, cols = sh["rows"], 2 + (1 if sh["payload_bytes"] else 0)
    flops = bytes_ = 0
    for n in sh["leaves"]:
        flops += 2 * cols * k * n + 2 * n
        bytes_ += k * n * (sh["delta_bytes"] + sh["payload_bytes"]) + F32 * n
    return float(flops), float(bytes_)


def superpose(sh) -> tuple:
    """eqs. 6 + 8: sum_k b_k p_k x_k plus the noise, over varsigma."""
    k = sh["rows"]
    pb = sh["payload_bytes"] or sh["delta_bytes"]
    flops = sum(2 * k * n + 2 * n for n in sh["leaves"])
    bytes_ = sum(k * n * pb + 2 * F32 * n for n in sh["leaves"])
    return float(flops), float(bytes_)


def gather_superpose(sh) -> tuple:
    """The compressed superposition: (m, s) values, their indices and
    per-row scales in, the (d,) noise in and the (d,) aggregate out."""
    m, s, d = sh["rows"], sh["s"], sum(sh["leaves"])
    flops = 2 * m * s + 2 * d
    bytes_ = m * s * (sh["slot_bytes"] + 4) + F32 * m + 2 * F32 * d
    return float(flops), float(bytes_)


def roofline_share(flops, bytes_, seconds, peak) -> float:
    """Percent of the chip's roofline: the least time the work could take
    at the bf16 peak or at HBM bandwidth, over the time it took."""
    ideal = max(flops / peak["bf16_flops_per_s"],
                bytes_ / peak["hbm_bytes_per_s"])
    return 100.0 * ideal / seconds
