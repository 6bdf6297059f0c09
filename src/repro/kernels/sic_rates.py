"""Pallas kernel for batched SIC weighted-sum-rate scoring (paper §III-A).

Layout (DESIGN.md §3 conventions): the (V, K) candidate batch is transposed
to (K, V) so the huge V axis rides the 128-wide lane dimension and K (<= 8
after padding) sits on sublanes; the grid streams (K_PAD, BLOCK_V) tiles.

Inside a tile the decode order is *not* materialized with a sort: K is tiny,
so the suffix interference sum is computed with the O(K^2) comparison matrix

    tail_i = sum_j rx_j * [ rx_j < rx_i  or  (rx_j == rx_i and j > i) ]

which is exactly "sum of receive powers decoded after i" under the
descending-rx, ties-by-lower-index order that the numpy engine
(``repro.core.rates``) and its jnp mirror (``repro.core.rates_jax``, the
device-resident MWIS greedy's scorer) use via a stable argsort.  The double
loop is
unrolled at trace time (K static), so the kernel is pure VPU elementwise
work — no gather, no sort network.

Zero-padded sublane rows (rx = w = 0) are decoded last among ties by the
j > i rule and carry zero weight, so padding never perturbs real rates.

The ``interpret`` flag is resolved by :mod:`repro.kernels.platform` from the
platform the call runs on.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.platform import resolve_interpret

LANE = 128          # TPU lane width
BLOCK_V = 512       # candidate groups per grid step (4 lanes of 128)
K_PAD = 8           # f32 sublane tile: pad the NOMA group axis to 8


def _sic_kernel(rx_ref, w_ref, o_ref, *, k: int, noise: float):
    rx = rx_ref[...].astype(jnp.float32)        # (K_PAD, BLOCK_V)
    w = w_ref[...].astype(jnp.float32)
    acc = jnp.zeros((1, rx.shape[1]), jnp.float32)
    for i in range(k):
        rxi = rx[i : i + 1, :]
        tail = jnp.zeros_like(rxi)
        for j in range(k):
            if j == i:
                continue
            rxj = rx[j : j + 1, :]
            decoded_after = (rxj < rxi) | ((rxj == rxi) & (j > i))
            tail = tail + jnp.where(decoded_after, rxj, 0.0)
        acc = acc + w[i : i + 1, :] * jnp.log2(1.0 + rxi / (tail + noise))
    o_ref[...] = acc


def sic_weighted_rates_pallas(
    powers_vk: jax.Array,
    gains_vk: jax.Array,
    weights_vk: jax.Array,
    noise_power: float,
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """(V, K) powers/gains/weights -> (V,) weighted SIC sum rates."""
    v, k = powers_vk.shape
    if k > K_PAD:
        raise ValueError(
            f"sic_weighted_rates_pallas supports NOMA groups of K <= {K_PAD} "
            f"(got K={k}); use the jnp reference path for larger groups"
        )
    if v == 0:
        # A grid of 0 blocks is illegal (padding can't grow an empty axis to
        # BLOCK_V); an empty candidate batch scores to an empty result.
        return jnp.zeros((0,), jnp.float32)
    rx = (powers_vk * gains_vk * gains_vk).astype(jnp.float32).T   # (K, V)
    w = weights_vk.astype(jnp.float32).T
    pad_v = (-v) % BLOCK_V
    rx = jnp.pad(rx, ((0, K_PAD - k), (0, pad_v)))
    w = jnp.pad(w, ((0, K_PAD - k), (0, pad_v)))
    vp = v + pad_v
    out = pl.pallas_call(
        functools.partial(_sic_kernel, k=k, noise=float(noise_power)),
        grid=(vp // BLOCK_V,),
        in_specs=[
            pl.BlockSpec((K_PAD, BLOCK_V), lambda i: (0, i)),
            pl.BlockSpec((K_PAD, BLOCK_V), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, BLOCK_V), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, vp), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(rx, w)
    return out[0, :v]
