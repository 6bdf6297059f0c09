"""Jit'd public wrappers around the Pallas kernels (with jnp fallback).

Shapes are massaged here: flatten -> pad to (rows, 128) with rows a multiple
of BLOCK_ROWS -> kernel -> unpad. ``use_pallas`` selects the Pallas path
(see :mod:`repro.kernels.platform` for where it runs); the default jnp path
is used inside large jitted train steps where XLA fusion is already optimal.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels import dorefa
from repro.kernels.aggregate import weighted_aggregate_pallas
from repro.kernels.dorefa import BLOCK_ROWS, LANE
from repro.kernels.flash_decode import flash_decode_pallas
from repro.kernels.sic_rates import sic_weighted_rates_pallas

_TILE = BLOCK_ROWS * LANE


def _to_tiles(flat: jax.Array):
    n = flat.shape[0]
    pad = (-n) % _TILE
    x = jnp.pad(flat, (0, pad))
    return x.reshape(-1, LANE), n


def _from_tiles(x2d: jax.Array, n: int):
    return x2d.reshape(-1)[:n]


def max_abs_scale(x: jax.Array) -> jax.Array:
    """Two-pass scheme, pass 1: per-tensor max-abs scale (XLA reduction)."""
    return jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32))), 1e-12)


@functools.partial(jax.jit, static_argnames=("bits", "use_pallas"))
def quantize_pack(flat: jax.Array, bits: int, *, use_pallas: bool = False):
    """Flat vector -> (codes int32 (padded 2D), scale). Static bits."""
    scale = max_abs_scale(flat)
    x2d, _ = _to_tiles(flat.astype(jnp.float32))
    if use_pallas:
        codes = dorefa.quantize_codes_pallas(x2d, scale, bits)
    else:
        codes = ref.quantize_codes_ref(x2d, bits, scale)
    return codes, scale


@functools.partial(jax.jit, static_argnames=("bits", "size", "use_pallas"))
def unpack_dequantize(
    codes2d: jax.Array, scale: jax.Array, bits: int, size: int,
    *, use_pallas: bool = False,
):
    if use_pallas:
        x2d = dorefa.dequantize_codes_pallas(codes2d, scale, bits)
    else:
        x2d = ref.dequantize_codes_ref(codes2d, bits, scale)
    return _from_tiles(x2d, size)


@functools.partial(jax.jit, static_argnames=("bits", "use_pallas"))
def quantize_dequantize(x: jax.Array, bits: int, *, use_pallas: bool = False):
    """Fused uplink simulation for one tensor (any shape)."""
    flat = x.reshape(-1)
    scale = max_abs_scale(flat)
    x2d, n = _to_tiles(flat)
    if use_pallas:
        y2d = dorefa.quantize_dequantize_pallas(x2d, scale, bits)
    else:
        y2d = ref.quantize_dequantize_ref(x2d, bits, scale)
    return _from_tiles(y2d, n).reshape(x.shape).astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("bits", "use_pallas"))
def weighted_aggregate(
    codes: jax.Array,    # (K, ...) int32 — stacked client payloads, any shape
    scales: jax.Array,   # (K,)
    weights: jax.Array,  # (K,)
    bits: int,
    *,
    use_pallas: bool = False,
):
    if use_pallas:
        return weighted_aggregate_pallas(codes, scales, weights, bits)
    k = codes.shape[0]
    return ref.weighted_aggregate_ref(
        codes.reshape(k, -1), scales, weights, bits
    ).reshape(codes.shape[1:])


@functools.partial(jax.jit, static_argnames=("noise_power", "use_pallas"))
def sic_weighted_rates(
    powers_vk: jax.Array,
    gains_vk: jax.Array,
    weights_vk: jax.Array,
    noise_power: float,
    *,
    use_pallas: bool = False,
):
    """Batched NOMA SIC group scoring: (V, K) rows -> (V,) weighted rates.

    The scheduler-side (control-plane) engine is ``repro.core.rates``; this
    is the accelerator mirror for scoring huge candidate batches on device
    (use_pallas selects the comparison-matrix Pallas kernel; the default
    path is the shared jnp engine in
    ``repro.core.rates_jax``, which also powers the device-resident MWIS
    greedy in ``repro.core.scheduling`` at float64).
    """
    if use_pallas:
        return sic_weighted_rates_pallas(
            powers_vk, gains_vk, weights_vk, noise_power
        )
    return ref.sic_weighted_rates_ref(powers_vk, gains_vk, weights_vk, noise_power)


@functools.partial(jax.jit, static_argnames=("use_pallas", "block_s"))
def flash_decode(q, k, v, valid_len, *, use_pallas: bool = False,
                 block_s: int = 256):
    """One-token GQA decode attention over a cache (serving hot loop).

    q: (B, Hkv, G, D); k, v: (B, S, Hkv, D); valid_len: scalar int32.
    use_pallas selects the Pallas flash-decode kernel.
    """
    if use_pallas:
        return flash_decode_pallas(q, k, v, jnp.asarray(valid_len),
                                   block_s=block_s)
    return ref.flash_decode_ref(q, k, v, valid_len)
