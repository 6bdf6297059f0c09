"""Shared neural building blocks (pure functions over param dicts).

Conventions:
  * activations layout (B, S, ...) with heads as (B, S, H, D) — MaxText-style.
  * all matmul params fp32, compute cast to bf16, reductions/softmax in fp32.
  * attention never materializes (S, S): online-softmax over KV chunks
    (lax.scan), which is the TPU-native flash formulation at the XLA level:
    memory O(S * chunk) per head, so 32k-token prefills fit.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.params import ParamSpec

COMPUTE_DTYPE = jnp.bfloat16

# --------------------------------------------------------------------------
# Activation-sharding hook (set by the launcher; identity on single device)
#
# Without explicit constraints the SPMD partitioner's strategy for the
# residual stream is underconstrained and degrades with depth (measured:
# 10 GiB -> 115 GiB of fp32 activation all-reduce going from 2 to 4 layers
# on llama-3.2-vision/prefill_32k in the dry-run). The
# launcher installs a hook that pins: residual (B,S,D) -> (batch, None,
# None); heads (B,S,H,K) -> (batch, None, tensor, None).
# --------------------------------------------------------------------------

_ACT_SHARDING_HOOK = None


def set_activation_sharding(hook):
    """hook: callable(x, kind) -> x, kind in {"residual", "heads"}."""
    global _ACT_SHARDING_HOOK
    _ACT_SHARDING_HOOK = hook


def constrain(x, kind: str):
    if _ACT_SHARDING_HOOK is None:
        return x
    return _ACT_SHARDING_HOOK(x, kind)


# --------------------------------------------------------------------------
# Norms
# --------------------------------------------------------------------------

def rmsnorm_schema(d: int):
    return {"scale": ParamSpec((d,), ("embed",), init="ones")}


def rmsnorm(p, x, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# Rotary position embeddings
# --------------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, D), positions: (B, S) or (S,). Rotates pairs (even, odd)."""
    d = x.shape[-1]
    half = d // 2
    freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions.astype(jnp.float32)[:, :, None] * freq[None, None, :]  # (B,S,half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# Chunked (online-softmax) attention
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttnMaskSpec:
    causal: bool = True
    window: Optional[int] = None        # sliding-window attention (mixtral)
    block_local: Optional[int] = None   # llama4 chunked-local attention


def _mask_block(q_pos, k_pos, spec: AttnMaskSpec):
    """(Sq, Sk) bool mask block from absolute positions."""
    m = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if spec.causal:
        m &= q_pos[:, None] >= k_pos[None, :]
    if spec.window is not None:
        m &= q_pos[:, None] - k_pos[None, :] < spec.window
    if spec.block_local is not None:
        m &= (q_pos[:, None] // spec.block_local) == (k_pos[None, :] // spec.block_local)
    return m


def chunked_attention(
    q: jax.Array,            # (B, Sq, H, D)
    k: jax.Array,            # (B, Sk, Hkv, D)
    v: jax.Array,            # (B, Sk, Hkv, D)
    *,
    mask_spec: AttnMaskSpec,
    q_offset: int | jax.Array = 0,
    kv_chunk: int = 1024,
    kv_valid_len: Optional[jax.Array] = None,  # decode: #valid cache slots
    scale: Optional[float] = None,             # None: 1/sqrt(D)
    compute_dtype=None,                        # None: COMPUTE_DTYPE
) -> jax.Array:
    """Grouped-query online-softmax attention, O(Sq * chunk) memory.

    GQA is computed grouped — Q reshaped to (B, Sq, Hkv, G, D) — so KV heads
    are never materialized repeated.  ``compute_dtype`` is the dtype of the
    q/k/v operands and the running output (scores and the softmax sums are
    float32 either way).
    """
    cd = COMPUTE_DTYPE if compute_dtype is None else compute_dtype
    b, sq, h, d = q.shape
    _, sk, hkv, _ = k.shape
    g = h // hkv
    qf = q.reshape(b, sq, hkv, g, d).astype(cd)
    scale = (1.0 / jnp.sqrt(d).astype(jnp.float32) if scale is None
             else jnp.float32(scale))

    n_chunks = (sk + kv_chunk - 1) // kv_chunk
    pad = n_chunks * kv_chunk - sk
    kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))).astype(cd)
    vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0))).astype(cd)
    kc = kp.reshape(b, n_chunks, kv_chunk, hkv, d).transpose(1, 0, 2, 3, 4)
    vc = vp.reshape(b, n_chunks, kv_chunk, hkv, d).transpose(1, 0, 2, 3, 4)

    q_pos = q_offset + jnp.arange(sq)

    def body(carry, xs):
        m_run, l_run, acc = carry
        idx, k_blk, v_blk = xs                      # (B, C, Hkv, D)
        k_pos = idx * kv_chunk + jnp.arange(kv_chunk)
        # scores: (B, Hkv, G, Sq, C) in fp32
        s = jnp.einsum("bqhgd,bchd->bhgqc", qf, k_blk).astype(jnp.float32) * scale
        mask = _mask_block(q_pos, k_pos, mask_spec)
        valid = k_pos < (sk if kv_valid_len is None else kv_valid_len)
        mask = mask & valid[None, :]
        s = jnp.where(mask[None, None, None], s, -jnp.inf)
        m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
        # guard: rows with everything masked keep m=-inf; exp(-inf - -inf)=nan
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(mask[None, None, None], p, 0.0)
        corr = jnp.where(jnp.isneginf(m_run), 0.0, jnp.exp(m_run - m_safe))
        l_new = l_run * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhgqc,bchd->bhgqd", p.astype(cd), v_blk)
        acc = acc * corr[..., None].astype(cd) + pv
        return (m_new, l_new, acc), None

    m0 = jnp.full((b, hkv, g, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, sq), jnp.float32)
    a0 = jnp.zeros((b, hkv, g, sq, d), cd)
    (m_f, l_f, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (jnp.arange(n_chunks), kc, vc)
    )
    denom = jnp.where(l_f > 0, l_f, 1.0)[..., None]
    out = (acc.astype(jnp.float32) / denom).astype(cd)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, d)  # (B,Sq,H,D)


# --------------------------------------------------------------------------
# Attention block (projections + rope + qk-norm + cache handling)
# --------------------------------------------------------------------------

def attention_schema(cfg, *, d_model=None, shards: int = 16):
    d = d_model or cfg.d_model
    h = cfg.padded_heads(shards)
    hkv = cfg.padded_kv_heads(shards)
    hd = cfg.resolved_head_dim
    sch = {
        "wq": ParamSpec((d, h, hd), ("embed", "heads", "kv")),
        "wk": ParamSpec((d, hkv, hd), ("embed", "heads", "kv")),
        "wv": ParamSpec((d, hkv, hd), ("embed", "heads", "kv")),
        "wo": ParamSpec((h, hd, d), ("heads", "kv", "embed")),
    }
    if cfg.qkv_bias:
        sch["bq"] = ParamSpec((h, hd), ("heads", "kv"), init="zeros")
        sch["bk"] = ParamSpec((hkv, hd), ("heads", "kv"), init="zeros")
        sch["bv"] = ParamSpec((hkv, hd), ("heads", "kv"), init="zeros")
    if cfg.qk_norm:
        sch["q_norm"] = ParamSpec((hd,), (None,), init="ones")
        sch["k_norm"] = ParamSpec((hd,), (None,), init="ones")
    return sch


def _qk_head_norm(x, scale, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(x.dtype)


def attention_block(
    p,
    x: jax.Array,                  # (B, S, D)
    cfg,
    *,
    mask_spec: AttnMaskSpec,
    positions: Optional[jax.Array] = None,
    cache: Optional[dict] = None,  # {"k","v": (B, Smax, Hkv, hd), "len": scalar}
    kv_chunk: int = 1024,
    kv_source: Optional[jax.Array] = None,  # cross-attention memory (B, Sm, D)
):
    """Returns (out (B,S,D), new_cache)."""
    xc = x.astype(COMPUTE_DTYPE)
    src = xc if kv_source is None else kv_source.astype(COMPUTE_DTYPE)
    q = constrain(jnp.einsum("bsd,dhk->bshk", xc, p["wq"].astype(COMPUTE_DTYPE)), "heads")
    k = constrain(jnp.einsum("bsd,dhk->bshk", src, p["wk"].astype(COMPUTE_DTYPE)), "heads")
    v = constrain(jnp.einsum("bsd,dhk->bshk", src, p["wv"].astype(COMPUTE_DTYPE)), "heads")
    if cfg.qkv_bias:
        q = q + p["bq"].astype(COMPUTE_DTYPE)
        k = k + p["bk"].astype(COMPUTE_DTYPE)
        v = v + p["bv"].astype(COMPUTE_DTYPE)
    if cfg.qk_norm:
        q = _qk_head_norm(q, p["q_norm"], cfg.norm_eps)
        k = _qk_head_norm(k, p["k_norm"], cfg.norm_eps)

    use_rope = kv_source is None  # no rope on cross-attention memories
    if positions is None:
        positions = jnp.arange(x.shape[1])
    if use_rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)

    new_cache = None
    q_offset = 0
    kv_valid = None
    if cache is not None:
        if "k" in cache and kv_source is None:
            idx = cache["len"]
            ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                              (0, idx, 0, 0))
            cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                              (0, idx, 0, 0))
            k, v = ck, cv
            kv_valid = idx + x.shape[1]
            q_offset = idx
            new_cache = {"k": ck, "v": cv, "len": kv_valid}
        else:
            new_cache = cache

    out = chunked_attention(
        q, k, v,
        mask_spec=mask_spec if kv_source is None else AttnMaskSpec(causal=False),
        q_offset=q_offset, kv_chunk=kv_chunk, kv_valid_len=kv_valid,
    )
    y = constrain(jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(COMPUTE_DTYPE)),
                  "residual")
    return y.astype(x.dtype), new_cache


def init_attn_cache(cfg, batch: int, max_len: int, *, shards: int = 16):
    hkv = cfg.padded_kv_heads(shards)
    hd = cfg.resolved_head_dim
    return {
        "k": jnp.zeros((batch, max_len, hkv, hd), COMPUTE_DTYPE),
        "v": jnp.zeros((batch, max_len, hkv, hd), COMPUTE_DTYPE),
        "len": jnp.zeros((), jnp.int32),
    }


# --------------------------------------------------------------------------
# Gated MLP (SwiGLU) and embeddings
# --------------------------------------------------------------------------

def mlp_schema(d: int, d_ff: int):
    return {
        "wi_gate": ParamSpec((d, d_ff), ("embed", "mlp")),
        "wi_up": ParamSpec((d, d_ff), ("embed", "mlp")),
        "wo": ParamSpec((d_ff, d), ("mlp", "embed")),
    }


def mlp_block(p, x):
    xc = x.astype(COMPUTE_DTYPE)
    gate = jnp.einsum("bsd,df->bsf", xc, p["wi_gate"].astype(COMPUTE_DTYPE))
    up = jnp.einsum("bsd,df->bsf", xc, p["wi_up"].astype(COMPUTE_DTYPE))
    act = jax.nn.silu(gate.astype(jnp.float32)).astype(COMPUTE_DTYPE) * up
    out = jnp.einsum("bsf,fd->bsd", act, p["wo"].astype(COMPUTE_DTYPE))
    return constrain(out, "residual").astype(x.dtype)


def embedding_schema(vocab: int, d: int, *, tie: bool):
    sch = {"tokens": ParamSpec((vocab, d), ("vocab", "embed"), init="embed")}
    if not tie:
        sch["unembed"] = ParamSpec((d, vocab), ("embed", "vocab"))
    return sch


def embed(p, tokens):
    # optimization_barrier pins the table convert BEFORE the gather: without
    # it XLA converts after the gather and the vocab-shard partial-sum
    # all-reduce of the (B, S, D) activations runs in fp32 (2x bytes).
    # JAX batches and differentiates the
    # barrier itself (the FL engine vmaps and grads through this forward).
    table = jax.lax.optimization_barrier(p["tokens"].astype(COMPUTE_DTYPE))
    return constrain(table[tokens], "residual")


def unembed(p, x, *, tie: bool):
    xc = x.astype(COMPUTE_DTYPE)
    if tie:
        w = p["tokens"].astype(COMPUTE_DTYPE).T
    else:
        w = p["unembed"].astype(COMPUTE_DTYPE)
    return jnp.einsum("bsd,dv->bsv", xc, w).astype(jnp.float32)


def cross_entropy(logits: jax.Array, labels: jax.Array, *, vocab_size: int):
    """Mean NLL; positions with label < 0 are masked; padded vocab excluded.

    Written sharding-aware: the gold logit is extracted with an iota-match
    contraction rather than take_along_axis — a vocab-dim gather forces SPMD
    to all-gather the full (B, S, V) fp32 logits across the tensor axis
    (74 GiB/step on qwen2-0.5b/train_4k in the dry-run),
    while the contraction reduces shard-locally and all-reduces only (B, S).
    """
    logits = logits.astype(jnp.float32)
    v = logits.shape[-1]
    vocab_pos = jax.lax.broadcasted_iota(jnp.int32, (v,), 0)
    if v > vocab_size:
        logits = jnp.where(vocab_pos < vocab_size, logits, -1e9)
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    logz = jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1)) + m[..., 0]
    hit = vocab_pos == jnp.maximum(labels, 0)[..., None]
    gold = jnp.sum(jnp.where(hit, logits, 0.0), axis=-1)
    nll = logz - gold
    mask = (labels >= 0).astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
