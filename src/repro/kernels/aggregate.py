"""Pallas kernel for the PS-side fused dequant + weighted aggregation.

Server aggregation (paper Algorithm 1 line 10): theta update is the weighted
sum of K dequantized client payloads. Fusing dequant+scale+sum keeps each
code tile in VMEM exactly once instead of K separate dequant passes +
K-way add in HBM.

Tiling: codes are flattened to (K, R, 128) with R padded up to a multiple of
BLOCK_ROWS (the pad is sliced back off, so arbitrary payload sizes work);
each grid step loads a (K, BLOCK_ROWS, 128) brick (K <= 16 in practice, so
the brick stays well under VMEM limits) and reduces over K in registers.

Two dequant modes: a single static ``bits`` (every client quantized alike,
the historical API) or a per-client ``levels`` vector a_k = 2^{b_k} - 1 for
the batched FL engine's traced adaptive bit-widths.  Codes may be int32
(packed payloads) or float32 (traced codes where b_k can reach 32 and
2^32 - 1 no longer fits an int32).

Transformer-scale payloads (10^6-10^8 params) additionally chunk over the
parameter axis: above ``chunk_elems`` per client, the flattened (K, N)
matrix is processed as a ``lax.map`` over (K, chunk_elems) slabs, so the
padded tile grid for the whole payload is never materialized at once
(benchmarks/payload_bench.py measures this against the XLA einsum —
BENCH_payload.json).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dorefa import BLOCK_ROWS, LANE
from repro.kernels.platform import resolve_interpret

TILE_ELEMS = BLOCK_ROWS * LANE

DEFAULT_CHUNK_ELEMS = 64 * TILE_ELEMS   # ~2.1M elems (8.4 MB f32) per client
# Auto-chunk threshold: a multiple of the tile grid, deliberately larger
# than any LeNet-300-100 leaf (max 235,200 elems), so every pre-existing
# call site keeps tracing the identical unchunked program bit for bit.


def _aggregate_kernel(c_ref, coeff_ref, o_ref, *, k: int):
    # c_ref: (K, BLOCK_ROWS, LANE) codes; coeff_ref: (K,) scale*weight/a
    acc = jnp.zeros((c_ref.shape[1], c_ref.shape[2]), jnp.float32)
    for i in range(k):  # K is small and static: unrolled VPU adds
        acc = acc + c_ref[i, :, :].astype(jnp.float32) * coeff_ref[i]
    o_ref[...] = acc


def _aggregate_block(flat, coeff, *, interpret):
    """One padded-tile-grid pallas_call over a (K, n) slab; returns (n,)."""
    k, n = flat.shape
    pad = (-n) % TILE_ELEMS
    padded = jnp.pad(flat, ((0, 0), (0, pad)))
    tiles = padded.reshape(k, -1, LANE)
    rows = tiles.shape[1]
    grid = (rows // BLOCK_ROWS,)
    out = pl.pallas_call(
        functools.partial(_aggregate_kernel, k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((k, BLOCK_ROWS, LANE), lambda i: (0, i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),   # (K,) coefficients
        ],
        out_specs=pl.BlockSpec((BLOCK_ROWS, LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANE), jnp.float32),
        interpret=interpret,
    )(tiles, coeff)
    return out.reshape(-1)[:n]


def weighted_aggregate_pallas(
    codes: jax.Array,     # (K, ...) int32 or float32 codes, any trailing shape
    scales: jax.Array,    # (K,)
    weights: jax.Array,   # (K,)
    bits: int | None = None,
    *,
    levels: jax.Array | None = None,  # (K,) per-client a = 2^b - 1 (traced ok)
    interpret: bool | None = None,
    chunk_elems: int | None = None,
) -> jax.Array:
    """sum_k w_k * scale_k * codes_k / a_k, shaped like ``codes[0]``.

    Exactly one of ``bits`` (static, shared by all clients) or ``levels``
    (per-client, may be traced) selects the dequant divisor.  Payloads of
    any size are padded to the (BLOCK_ROWS, LANE) tile grid internally and
    the pad is sliced off the result; K = 1 and empty payloads are legal.

    ``chunk_elems`` (default :data:`DEFAULT_CHUNK_ELEMS`) caps the
    per-client slab a single pallas_call sees: payloads above it are
    reduced chunk by chunk under ``jax.lax.map``, so only one
    (K, chunk_elems) brick is tile-padded and resident at a time.  Chunk
    boundaries don't touch the math — each output element is still one
    K-term dot — so chunked and unchunked calls agree exactly.
    """
    if (bits is None) == (levels is None):
        raise ValueError("pass exactly one of bits= or levels=")
    interpret = resolve_interpret(interpret)
    k = codes.shape[0]
    out_shape = codes.shape[1:]
    n = 1
    for d in out_shape:
        n *= int(d)
    if k == 0 or n == 0:
        return jnp.zeros(out_shape, jnp.float32)
    if levels is None:
        levels = jnp.full((k,), float(2 ** int(bits) - 1), jnp.float32)
    coeff = (
        scales.astype(jnp.float32)
        * weights.astype(jnp.float32)
        / levels.astype(jnp.float32)
    )
    flat = codes.reshape(k, n)
    if chunk_elems is None:
        chunk_elems = DEFAULT_CHUNK_ELEMS
    chunk_elems = max(int(chunk_elems), TILE_ELEMS)
    if n <= chunk_elems:
        return _aggregate_block(
            flat, coeff, interpret=interpret
        ).reshape(out_shape)
    # Chunked path: pad the parameter axis to a chunk multiple ONCE, fold
    # it to (C, K, chunk) slabs, and let lax.map drive one block program
    # over them (compiled once, executed C times; peak live tile grid is
    # one chunk's, not the payload's).
    pad = (-n) % chunk_elems
    flat = jnp.pad(flat, ((0, 0), (0, pad)))
    slabs = flat.reshape(k, -1, chunk_elems).transpose(1, 0, 2)
    out = jax.lax.map(
        lambda slab: _aggregate_block(slab, coeff, interpret=interpret),
        slabs,
    )
    return out.reshape(-1)[:n].reshape(out_shape)


def _ota_kernel(c_ref, coeff_ref, n_ref, o_ref, *, k: int):
    # c_ref: (K, BLOCK_ROWS, LANE) raw updates; coeff_ref: (K,) masked
    # w_k / sum(w); n_ref: (BLOCK_ROWS, LANE) pre-scaled receiver noise
    acc = n_ref[...].astype(jnp.float32)
    for i in range(k):  # K is small and static: unrolled VPU adds
        acc = acc + c_ref[i, :, :].astype(jnp.float32) * coeff_ref[i]
    o_ref[...] = acc


def _ota_block(flat, coeff, noise, *, interpret):
    """One fused scale+superpose+denoise pallas_call over a (K, n) slab
    plus its (n,) noise strip; returns (n,)."""
    k, n = flat.shape
    pad = (-n) % TILE_ELEMS
    padded = jnp.pad(flat, ((0, 0), (0, pad)))
    tiles = padded.reshape(k, -1, LANE)
    noise_tiles = jnp.pad(noise, (0, pad)).reshape(-1, LANE)
    rows = tiles.shape[1]
    grid = (rows // BLOCK_ROWS,)
    out = pl.pallas_call(
        functools.partial(_ota_kernel, k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((k, BLOCK_ROWS, LANE), lambda i: (0, i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),   # (K,) coefficients
            pl.BlockSpec((BLOCK_ROWS, LANE), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_ROWS, LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANE), jnp.float32),
        interpret=interpret,
    )(tiles, coeff, noise_tiles)
    return out.reshape(-1)[:n]


def ota_aggregate_pallas(
    deltas: jax.Array,    # (K, ...) raw float client updates, any trailing shape
    coeff: jax.Array,     # (K,) masked OTA weights w_k / sum_A(w) (traced ok)
    noise: jax.Array,     # flattened receiver noise, already 1/sqrt(eta)-scaled
    *,
    interpret: bool | None = None,
    chunk_elems: int | None = None,
) -> jax.Array:
    """sum_k coeff_k * deltas_k + noise, shaped like ``deltas[0]``.

    The over-the-air receiver reduction (core/ota.py signal model): no
    dequant divisor — updates go over the air in analog, so the kernel
    fuses the FedAvg scaling, the superposition sum, and the additive
    receiver noise into one pass per tile.  ``noise`` must carry the full
    1/(sqrt(eta) * sum w) referral already (it is data, not a kernel
    parameter) and is flattened to the payload length.  The XLA einsum
    ``einsum("k,kn->n", coeff, flat) + noise`` is the equality oracle.

    K = 0 rounds degenerate to the bare noise floor; payloads above
    ``chunk_elems`` reuse the (K, chunk) slab layout of
    :func:`weighted_aggregate_pallas` with the noise strip chunked
    alongside, so only one brick is tile-padded at a time.
    """
    interpret = resolve_interpret(interpret)
    k = deltas.shape[0]
    out_shape = deltas.shape[1:]
    n = 1
    for d in out_shape:
        n *= int(d)
    if n == 0:
        return jnp.zeros(out_shape, jnp.float32)
    noise = noise.reshape(-1).astype(jnp.float32)
    if k == 0:
        return noise[:n].reshape(out_shape)
    flat = deltas.reshape(k, n)
    coeff = coeff.astype(jnp.float32)
    if chunk_elems is None:
        chunk_elems = DEFAULT_CHUNK_ELEMS
    chunk_elems = max(int(chunk_elems), TILE_ELEMS)
    if n <= chunk_elems:
        return _ota_block(
            flat, coeff, noise, interpret=interpret
        ).reshape(out_shape)
    pad = (-n) % chunk_elems
    flat = jnp.pad(flat, ((0, 0), (0, pad)))
    noise = jnp.pad(noise, (0, pad))
    slabs = flat.reshape(k, -1, chunk_elems).transpose(1, 0, 2)
    noise_slabs = noise.reshape(-1, chunk_elems)
    out = jax.lax.map(
        lambda sn: _ota_block(sn[0], coeff, sn[1], interpret=interpret),
        (slabs, noise_slabs),
    )
    return out.reshape(-1)[:n].reshape(out_shape)
