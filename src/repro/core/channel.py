"""Wireless channel model (paper §II-A).

Channel gain of device k at round t:  h_k^t = L_k^t * h0^t
  - L_k^t : large-scale free-space path loss
        L = sqrt(delta * lambda^2) / (4*pi*d^(alpha/2))
    (the paper writes L = sqrt(delta)*lambda / (4 pi d^{alpha/2}); delta is the
    combined antenna gain, lambda the carrier wavelength, d the PS distance,
    alpha the path-loss exponent).
  - h0^t : small-scale Rayleigh fading, h0 ~ CN(0, 1).

All quantities are vectorized over devices and generated with explicit JAX PRNG
keys so every simulation is reproducible.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

# Speed of light (m/s).
_C = 299_792_458.0


@dataclasses.dataclass(frozen=True)
class CellConfig:
    """Static description of the cell (paper §IV settings by default)."""

    num_devices: int = 300          # M
    cell_radius_m: float = 500.0    # PS cell size
    min_distance_m: float = 10.0    # keep devices out of the antenna near field
    carrier_hz: float = 2.4e9       # typical ISM carrier (paper does not state one)
    path_loss_exp: float = 3.0      # alpha
    antenna_gain: float = 1.0       # delta (unit gain)
    bandwidth_hz: float = 4e6       # uplink bandwidth B
    noise_dbm_per_hz: float = -174.0
    max_power_w: float = 0.01       # p^max
    slot_seconds: float = 0.2       # uplink slot t
    downlink_bandwidth_hz: float = 10e6
    downlink_power_w: float = 0.2

    @property
    def wavelength_m(self) -> float:
        return _C / self.carrier_hz

    @property
    def noise_power_w(self) -> float:
        """Total noise power over the uplink band: sigma^2 = N0 * B (watts)."""
        n0_w_per_hz = 10.0 ** (self.noise_dbm_per_hz / 10.0) * 1e-3
        return n0_w_per_hz * self.bandwidth_hz


def position_uniforms(key: jax.Array, num_devices: int) -> jax.Array:
    """The (M,) uniform draws behind :func:`sample_positions`."""
    k1, _ = jax.random.split(key)
    return jax.random.uniform(k1, (num_devices,))


def distances_from_uniforms(u: jax.Array, cfg: CellConfig) -> jax.Array:
    """Uniform over the disk => CDF(r) = r^2 / R^2 => r = R * sqrt(u)."""
    r = cfg.cell_radius_m * jnp.sqrt(u)
    return jnp.maximum(r, cfg.min_distance_m)


def sample_positions(key: jax.Array, cfg: CellConfig) -> jax.Array:
    """Uniformly distribute devices in the cell disk. Returns distances (M,)."""
    return distances_from_uniforms(position_uniforms(key, cfg.num_devices),
                                   cfg)


def large_scale_gain(distances_m: jax.Array, cfg: CellConfig) -> jax.Array:
    """Free-space path-loss amplitude gain L_k (linear, amplitude domain)."""
    num = jnp.sqrt(cfg.antenna_gain) * cfg.wavelength_m
    den = 4.0 * jnp.pi * distances_m ** (cfg.path_loss_exp / 2.0)
    return num / den


def fading_normals(key: jax.Array, shape) -> tuple:
    """The standard normal (re, im) draws behind :func:`sample_small_scale`."""
    kr, ki = jax.random.split(key)
    return jax.random.normal(kr, shape), jax.random.normal(ki, shape)


def rayleigh_magnitude(re: jax.Array, im: jax.Array) -> jax.Array:
    """|h0| of h0 = (re + j im) / sqrt(2) from standard normal draws."""
    half = jnp.sqrt(0.5)
    re = re * half
    im = im * half
    return jnp.sqrt(re**2 + im**2)


def sample_small_scale(key: jax.Array, shape) -> jax.Array:
    """|h0| with h0 ~ CN(0,1) (Rayleigh magnitude, E[|h0|^2] = 1)."""
    return rayleigh_magnitude(*fading_normals(key, shape))


def round_fading_normals(
    key: jax.Array, num_devices: int, num_rounds: int
) -> tuple:
    """The (T, M) normal pairs behind :func:`sample_round_channels`."""
    keys = jax.random.split(key, num_rounds)
    return jax.vmap(fading_normals, in_axes=(0, None))(keys, (num_devices,))


def sample_round_channels(
    key: jax.Array, distances_m: jax.Array, cfg: CellConfig, num_rounds: int
) -> jax.Array:
    """Channel gains h = L(d) * |h0| for every round: (T, M). Block fading
    across rounds."""
    re, im = round_fading_normals(key, distances_m.shape[0], num_rounds)
    return large_scale_gain(distances_m, cfg) * rayleigh_magnitude(re, im)


def downlink_time_seconds(
    model_bits: float, gains: jax.Array, cfg: CellConfig
) -> float:
    """Broadcast time T_d = max_k I / (B_d log2(1 + p_d * gamma_k)) (paper §IV).

    gamma_k is the received downlink SNR at device k.  Computed in float64
    like the uplink rate engine: squaring a far device's gain under a high
    ``path_loss_exp`` underflows float32, and ``log1p`` keeps the rate
    nonzero for SNRs below the 1 + x rounding threshold — either failure
    used to return ``inf`` and silently poison the Fig. 5 time axis.  A
    genuinely unreachable device (zero gain) raises instead.
    """
    n0_w_per_hz = 10.0 ** (cfg.noise_dbm_per_hz / 10.0) * 1e-3
    noise = n0_w_per_hz * cfg.downlink_bandwidth_hz
    g = np.asarray(gains, np.float64)
    snr = cfg.downlink_power_w * g * g / noise
    if not np.all(np.isfinite(snr)):
        raise ValueError(
            "non-finite downlink SNR: some channel gain is NaN/inf; check "
            "the upstream gain computation"
        )
    if not np.all(snr > 0.0):
        raise ValueError(
            "zero downlink SNR: some device has zero channel gain, so the "
            "broadcast never completes (T_d = inf); check the cell geometry"
        )
    rate = cfg.downlink_bandwidth_hz * np.log1p(snr) / np.log(2.0)
    return float(np.max(model_bits / rate))
