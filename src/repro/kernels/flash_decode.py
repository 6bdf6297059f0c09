"""Pallas TPU flash-decode attention kernel (beyond-paper, serving path).

One-token grouped-query attention over a long KV cache — the memory-bound
inner loop of decode_32k / long_500k. Tiling: grid (B, Hkv, S-blocks); each
(batch, kv-head) instance streams (BLOCK_S, D) cache tiles HBM->VMEM and
maintains the online-softmax state (m, l, acc) in VMEM scratch across the
sequential minor grid dimension — the canonical TPU flash-decode schedule.
Invalid cache tail (positions >= valid_len) is masked, and fully-invalid
blocks short-circuit via @pl.when (no MXU work issued).

Validated against ref.flash_decode_ref (tests/test_kernels.py); the
``interpret`` flag is resolved by :mod:`repro.kernels.platform`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import resolve_interpret

BLOCK_S = 256
NEG_INF = -1e30


def _kernel(vl_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, block_s: int, n_blocks: int):
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    valid_len = vl_ref[0]
    block_start = i * block_s

    @pl.when(block_start < valid_len)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (G, D)
        k = k_ref[0, :, 0, :].astype(jnp.float32)    # (BS, D)
        v = v_ref[0, :, 0, :].astype(jnp.float32)
        d = q.shape[-1]
        s = q @ k.T * (1.0 / (d ** 0.5))             # (G, BS)
        pos = block_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < valid_len, s, NEG_INF)

        m_old = m_ref[:, 0]                          # (G,)
        m_new = jnp.maximum(m_old, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(pos < valid_len, p, 0.0)
        corr = jnp.exp(m_old - m_new)
        l_new = l_ref[:, 0] * corr + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + p @ v
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(i == n_blocks - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[:, 0], 1e-30)[:, None]
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_decode_pallas(
    q: jax.Array,          # (B, Hkv, G, D) current-token queries, grouped
    k: jax.Array,          # (B, S, Hkv, D) cache
    v: jax.Array,          # (B, S, Hkv, D)
    valid_len: jax.Array,  # scalar int32: #valid cache positions
    *,
    block_s: int = BLOCK_S,
    interpret: bool | None = None,
) -> jax.Array:
    b, hkv, g, d = q.shape
    s = k.shape[1]
    assert s % block_s == 0, (s, block_s)
    n_blocks = s // block_s
    grid = (b, hkv, n_blocks)
    return pl.pallas_call(
        functools.partial(_kernel, block_s=block_s, n_blocks=n_blocks),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),                  # valid_len
            pl.BlockSpec((1, 1, g, d), lambda bi, hi, si: (bi, hi, 0, 0)),
            pl.BlockSpec((1, block_s, 1, d), lambda bi, hi, si: (bi, si, hi, 0)),
            pl.BlockSpec((1, block_s, 1, d), lambda bi, hi, si: (bi, si, hi, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), lambda bi, hi, si: (bi, hi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, 128), jnp.float32),   # m (running max, lane-bcast)
            pltpu.VMEM((g, 128), jnp.float32),   # l (running denom)
            pltpu.VMEM((g, d), jnp.float32),     # acc
        ],
        interpret=resolve_interpret(interpret),
    )(valid_len.reshape(1).astype(jnp.int32), q, k, v)
