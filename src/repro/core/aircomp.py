"""Over-the-air computation (AirComp) channel model — Section II-C.

Implements the paper's uplink MAC model exactly:
  * Rayleigh fading, i.i.d. across rounds (Sec. II-C);
  * transmitter pre-scaling phi_k = b_k p_k h_k^H / |h_k|^2  (eq. 5) — with
    perfect CSI the phase cancels, so only |h_k| matters (DESIGN.md §3);
  * received superposition y = sum_k b_k p_k w_k + n,  n ~ N(0, sigma_n^2 I)
    (eq. 6), sigma_n^2 = B * N0 (bandwidth x noise PSD);
  * server normalization w = y / sum_k b_k p_k  (eq. 8), giving aggregation
    weights alpha_k = b_k p_k / sum_i b_i p_i.

TPU adaptation (DESIGN.md §3): the superposition is the wireless analogue of
an all-reduce; ``repro.core.aggregation`` runs the same math as a masked
weighted psum over the client mesh axis, and ``repro.kernels.aircomp_sum``
provides the fused Pallas kernel for the stacked (K, D) form used here.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp


# Smallest meaningful eq.-8 normalizer sum_k b_k p_k. Used both as the
# division clamp and as the zero-uploader threshold: at or below it nothing
# superposed this period, so the received y is pure AWGN and normalizing it
# would overwrite w_g with ~1/VARSIGMA_MIN-amplified noise — the round must
# hold the global instead (repro.core.aggregation.guarded_global_update).
VARSIGMA_MIN = 1e-12


def dbm_per_hz_to_watts(n0_dbm_hz: float) -> float:
    """-174 dBm/Hz -> Watts/Hz."""
    return 10.0 ** ((n0_dbm_hz - 30.0) / 10.0)


@dataclass(frozen=True)
class ChannelConfig:
    """Section IV-A settings by default."""
    bandwidth_hz: float = 20e6
    n0_dbm_hz: float = -174.0
    p_max_watts: float = 15.0
    rayleigh_scale: float = 1.0

    @property
    def sigma_n2(self) -> float:
        """Noise power sigma_n^2 = B * N0 (Watts)."""
        return self.bandwidth_hz * dbm_per_hz_to_watts(self.n0_dbm_hz)

    @property
    def sigma_n(self) -> float:
        return float(jnp.sqrt(self.sigma_n2))


def sample_channel_gains(key, k: int, chan: ChannelConfig):
    """|h_k| ~ Rayleigh(scale): magnitude of CN(0, 2*scale^2)."""
    u = jax.random.uniform(key, (k,), minval=1e-6, maxval=1.0)
    return chan.rayleigh_scale * jnp.sqrt(-2.0 * jnp.log(u))


def effective_power_cap(w_norm2, h_abs, p_max, eps: float = 1e-12):
    """Power constraint (7): ||phi_k w_k||^2 = p_k^2 ||w_k||^2 / |h_k|^2 <= P_max
    => p_k <= |h_k| sqrt(P_max / ||w_k||^2). Returns the per-client cap."""
    return h_abs * jnp.sqrt(p_max / jnp.maximum(w_norm2, eps))


def aircomp_aggregate(stacked: jnp.ndarray, powers: jnp.ndarray,
                      mask: jnp.ndarray, key, sigma_n: float,
                      use_kernel: bool = False):
    """Eq. (6)+(8): stacked (K, D) client payloads -> (D,) normalized aggregate.

    powers: (K,) transmit powers p_k; mask: (K,) in {0,1} ready bits b_k.
    Returns (aggregate, normalizer) where normalizer = sum_k b_k p_k.
    """
    bp = powers * mask
    varsigma = jnp.maximum(jnp.sum(bp), VARSIGMA_MIN)
    noise = sigma_n * jax.random.normal(key, stacked.shape[1:], stacked.dtype)
    if use_kernel:
        from repro.kernels.ops import aircomp_sum
        agg = aircomp_sum(stacked, bp, noise)
    else:
        agg = (jnp.einsum("k,kd->d", bp.astype(stacked.dtype), stacked,
                          precision=jax.lax.Precision.HIGHEST)
               + noise) / varsigma.astype(stacked.dtype)
    return agg, varsigma


def aggregation_weights(powers, mask):
    """alpha_k = b_k p_k / sum_i b_i p_i (eq. 8)."""
    bp = powers * mask
    return bp / jnp.maximum(jnp.sum(bp), VARSIGMA_MIN)


def equivalent_noise_var(sigma_n2: float, powers, mask, d: int):
    """E||n~||^2 = d sigma_n^2 / (sum b_k p_k)^2 — term (e) numerator basis."""
    s = jnp.maximum(jnp.sum(powers * mask), VARSIGMA_MIN)
    return d * sigma_n2 / (s * s)
