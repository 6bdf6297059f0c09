"""Pattern-driven hybrid: Mamba2 and attention mixers in the order a
config's ``layer_types`` lists them, each followed by a SwiGLU MLP, with
muP multipliers (granite-4.0-h; ``repro.configs.granite_4_0_h_micro``).

  x      = embedding_multiplier * E[t]
  layer  h += r * mixer(rmsnorm(h));  h += r * mlp(rmsnorm(h))
         r = residual_multiplier
  mlp    down(silu(g) * u), [g, u] = the two halves of one input
         projection, gate first
  mamba  fused in_proj -> [z, xBC, dt]; causal depthwise conv (bias) and
         silu on xBC; SSD (``mamba2.ssd_chunked``) with dt = softplus(dt +
         dt_bias), A = -exp(A_log) and the D skip; gated RMSNorm
         (``mamba2._gated_norm``); out_proj
  attn   causal GQA with no positional encoding (NoPE), softmax scale
         ``attention_multiplier``
  logits unembed(rmsnorm(h)) / logits_scaling, embedding tied

Every projection a LoRA adapter may target takes one: ``W x + s B(A x)``,
``s = alpha / rank``, the adapters a separate tree (:func:`lora_schema`)
so the base stays frozen.  Everything computes in float32 (the
parameters' dtype), matmuls at the backend's default precision.  Layers
run one after another (a Python loop: the two kinds hold different
parameters), each rematerialized in the backward pass.  The loss and the
accuracy read the head in token blocks (:func:`token_nll`,
:func:`token_hits`), so no (tokens, vocab) array of a whole batch is live.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import mamba2 as M
from repro.models.params import ParamSpec

KINDS = ("mamba", "attention")


def layer_name(i: int) -> str:
    return f"layer{i:02d}"


def _mamba_dims(cfg):
    d_in, nheads, head_dim, groups, state = M.dims(cfg)
    conv_dim = d_in + 2 * groups * state
    return d_in, nheads, head_dim, groups, state, conv_dim


def _mixer_schema(cfg, kind):
    d = cfg.d_model
    if kind == "mamba":
        d_in, nh, _, _, _, conv_dim = _mamba_dims(cfg)
        return {
            "in_proj": ParamSpec((d, d_in + conv_dim + nh), ("embed", "mlp")),
            "conv_w": ParamSpec((cfg.ssm_conv_width, conv_dim), (None, "mlp")),
            "conv_b": ParamSpec((conv_dim,), ("mlp",), init="zeros"),
            "dt_bias": ParamSpec((nh,), ("heads",), init="ssm_dt"),
            "a_log": ParamSpec((nh,), ("heads",), init="ssm_a"),
            "d_skip": ParamSpec((nh,), ("heads",), init="ones"),
            "norm": ParamSpec((d_in,), ("mlp",), init="ones"),
            "out_proj": ParamSpec((d_in, d), ("mlp", "embed")),
        }
    if kind == "attention":
        hd = cfg.resolved_head_dim
        q, kv = cfg.num_heads * hd, cfg.num_kv_heads * hd
        return {
            "q": ParamSpec((d, q), ("embed", "heads")),
            "k": ParamSpec((d, kv), ("embed", "heads")),
            "v": ParamSpec((d, kv), ("embed", "heads")),
            "o": ParamSpec((q, d), ("heads", "embed")),
        }
    raise ValueError(f"unknown layer kind {kind!r}; known: {KINDS}")


def schema(cfg, *, shards: int = 1):
    """The base: ``layerNN`` dicts in order, the tied embedding, the final
    norm (``shards`` is accepted for the registry and unused)."""
    del shards
    if len(cfg.layer_types) != cfg.num_layers:
        raise ValueError(
            f"{cfg.name}: layer_types lists {len(cfg.layer_types)} layers, "
            f"num_layers is {cfg.num_layers}")
    d = cfg.d_model
    sch = {"embed": {"tokens": ParamSpec((cfg.vocab_size, d),
                                         ("vocab", "embed"), init="embed")},
           "final_norm": L.rmsnorm_schema(d)}
    ff = cfg.d_ff
    for i, kind in enumerate(cfg.layer_types):
        sch[layer_name(i)] = {
            "input_norm": L.rmsnorm_schema(d),
            "mixer": _mixer_schema(cfg, kind),
            "post_norm": L.rmsnorm_schema(d),
            "mlp": {"w_in": ParamSpec((d, 2 * ff), ("embed", "mlp")),
                    "w_out": ParamSpec((ff, d), ("mlp", "embed"))},
        }
    return sch


LORA_TARGETS = {"mamba": ("in_proj", "out_proj"),
                "attention": ("q", "k", "v", "o")}


def lora_schema(cfg, rank: int):
    """Rank-``rank`` adapters on every mixer projection of
    ``LORA_TARGETS``: ``a`` (in, rank) uniform in +-1/sqrt(in), ``b``
    (rank, out) zeros, so a fresh adapter leaves the base's output as it
    is."""
    base = schema(cfg)
    out = {}
    for i, kind in enumerate(cfg.layer_types):
        mixer = base[layer_name(i)]["mixer"]
        out[layer_name(i)] = {
            t: {"a": ParamSpec((mixer[t].shape[0], rank), (None, None),
                               init="lora_a"),
                "b": ParamSpec((rank, mixer[t].shape[1]), (None, None),
                               init="zeros")}
            for t in LORA_TARGETS[kind]}
    return out


# --------------------------------------------------------------------------
# Layers
# --------------------------------------------------------------------------

def _proj(x, w, adapter, scale):
    y = jnp.einsum("...i,io->...o", x, w)
    if adapter is not None:
        low = jnp.einsum("...i,ir->...r", x, adapter["a"])
        y = y + scale * jnp.einsum("...r,ro->...o", low, adapter["b"])
    return y


def mamba_mixer(p, h, cfg, ad, scale):
    d_in, nh, hp, g, n, conv_dim = _mamba_dims(cfg)
    bsz, s, _ = h.shape
    zxbcdt = _proj(h, p["in_proj"], ad and ad["in_proj"], scale)
    z = zxbcdt[..., :d_in]
    xbc = jax.nn.silu(M._causal_conv(zxbcdt[..., d_in:d_in + conv_dim],
                                     p["conv_w"], p["conv_b"]))
    dt = jax.nn.softplus(zxbcdt[..., d_in + conv_dim:] + p["dt_bias"])
    xs = xbc[..., :d_in].reshape(bsz, s, nh, hp)
    b = xbc[..., d_in:d_in + g * n].reshape(bsz, s, g, n)
    c = xbc[..., d_in + g * n:].reshape(bsz, s, g, n)
    pad = (-s) % cfg.ssm_chunk
    widths = ((0, 0), (0, pad), (0, 0), (0, 0))
    y, _ = M.ssd_chunked(jnp.pad(xs, widths), jnp.pad(dt, widths[:3]),
                         p["a_log"], jnp.pad(b, widths), jnp.pad(c, widths),
                         chunk=cfg.ssm_chunk)
    y = y[:, :s] + p["d_skip"][None, None, :, None] * xs
    y = M._gated_norm(y.reshape(bsz, s, d_in), z, p["norm"], cfg.norm_eps)
    return _proj(y, p["out_proj"], ad and ad["out_proj"], scale)


def attention_mixer(p, h, cfg, ad, scale):
    bsz, s, _ = h.shape
    hd = cfg.resolved_head_dim

    def heads(t, n):
        return _proj(h, p[t], ad and ad[t], scale).reshape(bsz, s, n, hd)

    q = heads("q", cfg.num_heads)
    k, v = heads("k", cfg.num_kv_heads), heads("v", cfg.num_kv_heads)
    out = L.chunked_attention(
        q, k, v, mask_spec=L.AttnMaskSpec(causal=True), kv_chunk=s,
        scale=cfg.attention_multiplier, compute_dtype=jnp.float32)
    return _proj(out.reshape(bsz, s, -1), p["o"], ad and ad["o"], scale)


def mlp(p, h):
    gu = jnp.einsum("bsd,df->bsf", h, p["w_in"])
    g, u = jnp.split(gu, 2, axis=-1)
    return jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u, p["w_out"])


_MIXERS = {"mamba": mamba_mixer, "attention": attention_mixer}


def _layer(p, ad, x, *, cfg, kind, scale):
    r = cfg.residual_multiplier
    with jax.named_scope(kind):
        x = x + r * _MIXERS[kind](p["mixer"],
                                  L.rmsnorm(p["input_norm"], x, cfg.norm_eps),
                                  cfg, ad, scale)
    with jax.named_scope("mlp"):
        return x + r * mlp(p["mlp"], L.rmsnorm(p["post_norm"], x,
                                               cfg.norm_eps))


def hidden(params, tokens, cfg, *, lora=None, lora_scale=0.0, remat=True):
    """The final-normed hidden states (B, S, D), float32."""
    x = cfg.embedding_multiplier * params["embed"]["tokens"][tokens]
    for i, kind in enumerate(cfg.layer_types):
        name = layer_name(i)
        fn = functools.partial(_layer, cfg=cfg, kind=kind, scale=lora_scale)
        if remat:
            fn = jax.checkpoint(fn)
        x = fn(params[name], None if lora is None else lora[name], x)
    return L.rmsnorm(params["final_norm"], x, cfg.norm_eps)


def head(params, h, cfg):
    """Logits of hidden states: the tied embedding over logits_scaling."""
    return (jnp.einsum("...d,vd->...v", h, params["embed"]["tokens"])
            / cfg.logits_scaling)


def forward(params, tokens, cfg, *, lora=None, lora_scale=0.0, remat=True,
            **_):
    return head(params, hidden(params, tokens, cfg, lora=lora,
                               lora_scale=lora_scale, remat=remat), cfg), None


def _blocks(h, labels, block):
    """(nb, block, D) hidden rows and (nb, block) labels, padded with
    label -1."""
    d = h.shape[-1]
    hf, lf = h.reshape(-1, d), labels.reshape(-1)
    pad = (-hf.shape[0]) % block
    hf = jnp.pad(hf, ((0, pad), (0, 0)))
    lf = jnp.pad(lf, (0, pad), constant_values=-1)
    return hf.reshape(-1, block, d), lf.reshape(-1, block)


def token_nll(params, h, labels, cfg, *, block):
    """Sum of the next-token NLL over the positions with label >= 0, the
    head taken ``block`` tokens at a time (each block's logits recomputed
    in the backward pass)."""

    @jax.checkpoint
    def one(hb, lb):
        logits = head(params, hb, cfg)
        gold = jnp.take_along_axis(logits, jnp.maximum(lb, 0)[:, None],
                                   axis=-1)[:, 0]
        nll = jax.nn.logsumexp(logits, axis=-1) - gold
        return jnp.sum(jnp.where(lb >= 0, nll, 0.0))

    def body(acc, xs):
        return acc + one(*xs), None

    with jax.named_scope("blocked_head"):
        total, _ = jax.lax.scan(body, jnp.float32(0.0), _blocks(h, labels,
                                                                block))
    return total


def token_hits(params, h, labels, cfg, *, block):
    """How many positions with label >= 0 have their label as the argmax
    logit, the head taken ``block`` tokens at a time."""

    def body(acc, xs):
        hb, lb = xs
        pred = jnp.argmax(head(params, hb, cfg), axis=-1)
        return acc + jnp.sum((pred == lb) & (lb >= 0)), None

    with jax.named_scope("blocked_head"):
        hits, _ = jax.lax.scan(body, jnp.int32(0), _blocks(h, labels, block))
    return hits


def loss_fn(params, batch, cfg, **kw):
    logits, _ = forward(params, batch["tokens"], cfg, **kw)
    return L.cross_entropy(logits, batch["labels"], vocab_size=cfg.vocab_size)
