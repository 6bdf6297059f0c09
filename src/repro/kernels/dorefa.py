"""Pallas TPU kernels for DoReFa gradient quantization (paper §II-B).

TPU adaptation (DESIGN.md §3): the quantizer is pure VPU elementwise work.
We tile the flattened gradient as (rows, 128) — 128 matches the TPU lane
width — and stream (BLOCK_ROWS, 128) tiles HBM->VMEM per grid step. The
global max-abs scale is a cheap XLA reduction done by the ops.py wrapper
(two-pass scheme); the kernels are single-pass elementwise given the scale.

Each kernel's ``interpret`` flag is resolved by :mod:`repro.kernels.platform`
from the platform the call runs on.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import resolve_interpret

LANE = 128          # TPU lane width: last-dim tile must be a multiple
BLOCK_ROWS = 256    # (256, 128) fp32 tile = 128 KiB VMEM per operand


def _levels(bits: int) -> float:
    return float(2 ** int(bits) - 1)


# --------------------------------------------------------------------------
# quantize -> int32 codes
# --------------------------------------------------------------------------

def _quantize_kernel(x_ref, scale_ref, o_ref, *, a: float):
    x = x_ref[...].astype(jnp.float32)
    # Divide (not multiply-by-reciprocal): the reciprocal is 1 ulp off the
    # oracle's x / scale, which flips round() at exact .5 boundaries for
    # bits >= 16 (caught by test_pack_unpack_roundtrip[16]).
    xn = jnp.clip(x / jnp.maximum(scale_ref[0], 1e-12), -1.0, 1.0)
    # round-half-away-from-zero == jnp.round (banker's) differences only at
    # exact .5 of representable values; we match jnp.round for oracle parity.
    o_ref[...] = jnp.round(a * xn).astype(jnp.int32)


def quantize_codes_pallas(
    x2d: jax.Array, scale: jax.Array, bits: int,
    *, interpret: bool | None = None,
) -> jax.Array:
    """x2d: (R, 128) float -> (R, 128) int32 codes. R % BLOCK_ROWS == 0."""
    rows = x2d.shape[0]
    grid = (rows // BLOCK_ROWS,)
    return pl.pallas_call(
        functools.partial(_quantize_kernel, a=_levels(bits)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_ROWS, LANE), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),  # (1,) scale
        ],
        out_specs=pl.BlockSpec((BLOCK_ROWS, LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANE), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(x2d, scale.reshape(1))


# --------------------------------------------------------------------------
# dequantize codes -> float32
# --------------------------------------------------------------------------

def _dequantize_kernel(c_ref, scale_ref, o_ref, *, a: float):
    c = c_ref[...].astype(jnp.float32)
    o_ref[...] = c * (scale_ref[0] / a)


def dequantize_codes_pallas(
    codes2d: jax.Array, scale: jax.Array, bits: int,
    *, interpret: bool | None = None,
) -> jax.Array:
    rows = codes2d.shape[0]
    grid = (rows // BLOCK_ROWS,)
    return pl.pallas_call(
        functools.partial(_dequantize_kernel, a=_levels(bits)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_ROWS, LANE), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),  # (1,) scale
        ],
        out_specs=pl.BlockSpec((BLOCK_ROWS, LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANE), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(codes2d, scale.reshape(1))


# --------------------------------------------------------------------------
# fused quantize->dequantize (the in-train-step uplink simulation)
# --------------------------------------------------------------------------

def _qdq_kernel(x_ref, scale_ref, o_ref, *, a: float):
    x = x_ref[...].astype(jnp.float32)
    s = jnp.maximum(scale_ref[0], 1e-12)
    xn = jnp.clip(x / s, -1.0, 1.0)
    q = jnp.round(a * xn) / a
    o_ref[...] = (q * s).astype(o_ref.dtype)


def quantize_dequantize_pallas(
    x2d: jax.Array, scale: jax.Array, bits: int,
    *, interpret: bool | None = None,
) -> jax.Array:
    rows = x2d.shape[0]
    grid = (rows // BLOCK_ROWS,)
    return pl.pallas_call(
        functools.partial(_qdq_kernel, a=_levels(bits)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_ROWS, LANE), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),  # (1,) scale
        ],
        out_specs=pl.BlockSpec((BLOCK_ROWS, LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANE), x2d.dtype),
        interpret=resolve_interpret(interpret),
    )(x2d, scale.reshape(1))
