"""Plain reference of one FL horizon with federated LoRA over a frozen
granite-4.0-h-micro period (the configuration's catalog keys; Hugging Face
``granitemoehybrid``): the model's own equations, and its counts for the
benchmark's utilization.  The rest of the horizon (channels, schedule,
powers, budgets, uplink, the round loop) is the payload-free
``fl_horizon``.

Imports nothing of the program.  Straightforward ``jax.numpy`` in the
precision asked for (float32 for the check, bfloat16 for the control),
matmuls at ``jax.default_matmul_precision("highest")``, one client after
another.  Sizes from the configuration: ``hidden_size`` d,
``layer_types``, ``mamba_n_heads`` x ``mamba_d_head``, ``mamba_d_state``
N, ``mamba_d_conv``, ``num_attention_heads`` / ``num_key_value_heads``,
``shared_intermediate_size`` F, ``vocab_size`` V, and the adapters of
``model`` (rank r, alpha).

  x        embedding_multiplier * E[t]
  layer    h += residual_multiplier * mixer(rmsnorm(h))
           h += residual_multiplier * mlp(rmsnorm(h))
  rmsnorm  x / sqrt(mean(x^2) + rms_norm_eps) * w
  mlp      (silu(g) * u) W_out, [g, u] = x W_in (gate first)
  mamba    [z, xBC, dt] = x W_in; xBC = silu(causal depthwise conv, width
           mamba_d_conv, with bias); dt = softplus(dt + dt_bias);
           A = -exp(A_log); y_t = sum_{s <= t} (C_t . B_s)
           exp(sum_{s < k <= t} dt_k A) dt_s x_s + D x_t, taken whole over
           the sequence (a segment-sum decay mask over all positions, no
           chunks); y = rmsnorm(y * silu(z)) * w; out = y W_out
  attn     causal softmax(q k^T * attention_multiplier) v over GQA heads
           (query head i reads KV head i // (heads / kv_heads)), no
           positional encoding (position_embedding_type "nope")
  logits   rmsnorm(h) E^T / logits_scaling (tied embedding)
  LoRA     every mixer projection W in (Mamba in_proj, out_proj; attention
           q, k, v, o): x W + (alpha / r) (x A) B
  training minibatch SGD of the adapters on the mean next-token NLL of
           the real rows' positions (a row of S + 1 ids: inputs ids[:S],
           targets ids[1:]); the base never changes
  eval     next-token top-1 accuracy over the test rows' positions

The frozen base is rebuilt leaf by leaf from the derivation the program
documents (``repro.models.fl_models.LoRAFLModel``): key = PRNGKey(
20251002); the leaf at path p (``"['layer00']['mixer']['in_proj']"``)
from fold_in(key, crc32(p) mod 2**31): matrices truncated normal (+-3)
over sqrt(fan_in), fan_in their leading dimension; the embedding normal
x 0.02; norms and D ones; the conv bias zeros; A_log log U(1, 16);
dt_bias softplus^-1(dt), dt log-uniform on [1e-3, 1e-1].  The adapters
from the instance key by the same folding: A uniform within
+-1/sqrt(fan_in), B zeros.

Departures from the published model, all shared with the program: random
base weights from that key in place of the released checkpoint; one
period of the 40 layers; no dropout; a synthetic corpus (``worlds/
tokens.py``) in place of text.  Blocks that change no value: each layer
is rematerialized in the backward pass and the head is taken 512 tokens
at a time, so that a client's step fits beside the program's base.
"""
from __future__ import annotations

import dataclasses
import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.harness import BENCH_DIR, load_module

horizon = load_module(BENCH_DIR / "references" / "fl_horizon.py")

BASE_SEED = 20251002
HEAD_BLOCK = 512


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int
    vocab: int
    layer_types: tuple
    heads: int
    kv_heads: int
    head_dim: int
    m_heads: int
    m_head_dim: int
    state: int
    conv: int
    ff: int
    eps: float
    emb_mult: float
    res_mult: float
    logits_scaling: float
    attn_scale: float
    rank: int
    alpha: float

    @property
    def d_in(self):
        return self.m_heads * self.m_head_dim

    @property
    def conv_dim(self):
        return self.d_in + 2 * self.state

    @property
    def in_proj(self):
        return self.d_in + self.conv_dim + self.m_heads


def dims(config):
    m = config["model"]
    if config["mamba_n_groups"] != 1:
        raise ValueError("the reference takes one Mamba group")
    return Dims(
        d=config["hidden_size"], vocab=config["vocab_size"],
        layer_types=tuple(config["layer_types"]),
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"],
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        m_heads=config["mamba_n_heads"], m_head_dim=config["mamba_d_head"],
        state=config["mamba_d_state"], conv=config["mamba_d_conv"],
        ff=config["shared_intermediate_size"], eps=config["rms_norm_eps"],
        emb_mult=config["embedding_multiplier"],
        res_mult=config["residual_multiplier"],
        logits_scaling=config["logits_scaling"],
        attn_scale=config["attention_multiplier"],
        rank=m["lora_rank"], alpha=m["lora_alpha"])


def _matrices(dm, kind):
    """(name, fan_in, fan_out) of the mixer's projections."""
    if kind == "mamba":
        return [("in_proj", dm.d, dm.in_proj), ("out_proj", dm.d_in, dm.d)]
    q, kv = dm.heads * dm.head_dim, dm.kv_heads * dm.head_dim
    return [("q", dm.d, q), ("k", dm.d, kv), ("v", dm.d, kv), ("o", q, dm.d)]


# ---------------------------------------------------------------- counts

def param_count(config):
    """The adapters: what trains, and what the uplink carries."""
    dm = dims(config)
    return sum((fan_in + fan_out) * dm.rank
               for kind in dm.layer_types
               for _, fan_in, fan_out in _matrices(dm, kind))


def forward_macs(dm, seq):
    """Multiply-accumulates of one token's forward pass: every projection
    (adapters included), the Mamba conv, the SSD in its recurrent form
    (state update and read-out, 2 P N a head), causal attention at the
    mean context (seq + 1) / 2 (scores and values), the head."""
    total = dm.vocab * dm.d
    for kind in dm.layer_types:
        total += sum(fi * fo + (fi + fo) * dm.rank
                     for _, fi, fo in _matrices(dm, kind))
        total += 3 * dm.d * dm.ff
        if kind == "mamba":
            total += dm.conv * dm.conv_dim
            total += 2 * dm.m_heads * dm.m_head_dim * dm.state
        else:
            total += 2 * dm.heads * dm.head_dim * (seq + 1) / 2
    return total


def lora_weight_macs(dm):
    """Multiply-accumulates of one token's adapter weight gradients."""
    return sum((fi + fo) * dm.rank for kind in dm.layer_types
               for _, fi, fo in _matrices(dm, kind))


def sample_flops(config):
    """(train, eval) FLOPs of one real sample, a row of S tokens, 2 FLOPs
    a multiply-accumulate: eval is the forward pass; training adds the
    input gradient (counted as one forward pass: the base's weight
    gradients are never taken) and the adapters' weight gradients."""
    dm = dims(config)
    seq = config["model"]["row_tokens"] - 1
    fwd = forward_macs(dm, seq)
    return (2 * seq * (2 * fwd + lora_weight_macs(dm)), 2 * seq * fwd)


# ---------------------------------------------------------------- weights

def _key(root, path):
    return jax.random.fold_in(root, zlib.crc32(path.encode()) % (2**31))


def _normal(key, shape):
    std = 1.0 / np.sqrt(shape[0])
    return jax.random.truncated_normal(key, -3, 3, shape, jnp.float32) * std


def _ones(_, shape):
    return jnp.ones(shape, jnp.float32)


def _zeros(_, shape):
    return jnp.zeros(shape, jnp.float32)


def _a_log(key, shape):
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))


def _dt_bias(key, shape):
    u = jax.random.uniform(key, shape, jnp.float32)
    dt = jnp.exp(u * (jnp.log(0.1) - jnp.log(0.001)) + jnp.log(0.001))
    return dt + jnp.log(-jnp.expm1(-dt))


def _layer_leaves(dm, kind):
    ff = [("input_norm", "scale", _ones, (dm.d,)),
          ("post_norm", "scale", _ones, (dm.d,)),
          ("mlp", "w_in", _normal, (dm.d, 2 * dm.ff)),
          ("mlp", "w_out", _normal, (dm.ff, dm.d))]
    mixer = [("mixer", name, _normal, (fi, fo))
             for name, fi, fo in _matrices(dm, kind)]
    if kind == "mamba":
        mixer += [("mixer", "conv_w", _normal, (dm.conv, dm.conv_dim)),
                  ("mixer", "conv_b", _zeros, (dm.conv_dim,)),
                  ("mixer", "dt_bias", _dt_bias, (dm.m_heads,)),
                  ("mixer", "a_log", _a_log, (dm.m_heads,)),
                  ("mixer", "d_skip", _ones, (dm.m_heads,)),
                  ("mixer", "norm", _ones, (dm.d_in,))]
    return ff + mixer


def layer_name(i):
    return f"layer{i:02d}"


def frozen_base(dm):
    """The float32 base, leaf by leaf.  Built anew for each horizon and
    dropped after it: the program's own base and its horizon program's
    temporaries hold the rest of the chip's memory."""
    root = jax.random.PRNGKey(BASE_SEED)
    base = {"embed": {"tokens": jax.random.normal(
                _key(root, "['embed']['tokens']"), (dm.vocab, dm.d),
                jnp.float32) * 0.02},
            "final_norm": {"scale": jnp.ones((dm.d,), jnp.float32)}}
    for i, kind in enumerate(dm.layer_types):
        layer = base[layer_name(i)] = {}
        for group, name, init, shape in _layer_leaves(dm, kind):
            path = f"['{layer_name(i)}']['{group}']['{name}']"
            layer.setdefault(group, {})[name] = init(_key(root, path), shape)
    return base


def init_adapters(dm, key, dtype):
    out = {}
    for i, kind in enumerate(dm.layer_types):
        out[layer_name(i)] = {}
        for name, fan_in, fan_out in _matrices(dm, kind):
            path = f"['{layer_name(i)}']['{name}']"
            bound = 1.0 / np.sqrt(fan_in)
            a = jax.random.uniform(_key(key, path + "['a']"),
                                   (fan_in, dm.rank), jnp.float32,
                                   -bound, bound)
            out[layer_name(i)][name] = {
                "a": a.astype(dtype),
                "b": jnp.zeros((dm.rank, fan_out), dtype)}
    return out


# ---------------------------------------------------------------- model

def rmsnorm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + jnp.asarray(eps, x.dtype)) * w


def proj(x, w, ad, scale):
    return x @ w + scale * ((x @ ad["a"]) @ ad["b"])


def ssd_whole(x, dt, a, b, c):
    """x (B, S, H, P), dt (B, S, H), a (H,), b and c (B, S, N): the
    quadratic form over the whole sequence."""
    s = x.shape[1]
    cs = jnp.cumsum(dt * a, axis=1)                          # (B, S, H)
    seg = cs[:, :, None, :] - cs[:, None, :, :]              # (B, L, S, H)
    causal = jnp.tril(jnp.ones((s, s), bool))[None, :, :, None]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    cb = jnp.einsum("bln,bsn->bls", c, b)
    return jnp.einsum("bls,blsh,bshp->blhp", cb, decay, x * dt[..., None])


def mamba(p, ad, h, dm, scale):
    bsz, s, _ = h.shape
    zxbcdt = proj(h, p["in_proj"], ad["in_proj"], scale)
    z = zxbcdt[..., :dm.d_in]
    xbc = zxbcdt[..., dm.d_in:dm.d_in + dm.conv_dim]
    dt = jax.nn.softplus(zxbcdt[..., dm.d_in + dm.conv_dim:] + p["dt_bias"])
    padded = jnp.pad(xbc, ((0, 0), (dm.conv - 1, 0), (0, 0)))
    conv = sum(padded[:, i:i + s] * p["conv_w"][i] for i in range(dm.conv))
    xbc = jax.nn.silu(conv + p["conv_b"])
    x = xbc[..., :dm.d_in].reshape(bsz, s, dm.m_heads, dm.m_head_dim)
    b = xbc[..., dm.d_in:dm.d_in + dm.state]
    c = xbc[..., dm.d_in + dm.state:]
    y = ssd_whole(x, dt, -jnp.exp(p["a_log"]), b, c)
    y = (y + p["d_skip"][:, None] * x).reshape(bsz, s, dm.d_in)
    y = rmsnorm(y * jax.nn.silu(z), p["norm"], dm.eps)
    return proj(y, p["out_proj"], ad["out_proj"], scale)


def attention(p, ad, h, dm, scale):
    bsz, s, _ = h.shape
    q = proj(h, p["q"], ad["q"], scale).reshape(bsz, s, dm.heads, -1)
    k = proj(h, p["k"], ad["k"], scale).reshape(bsz, s, dm.kv_heads, -1)
    v = proj(h, p["v"], ad["v"], scale).reshape(bsz, s, dm.kv_heads, -1)
    k = jnp.repeat(k, dm.heads // dm.kv_heads, axis=2)
    v = jnp.repeat(v, dm.heads // dm.kv_heads, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * jnp.asarray(
        dm.attn_scale, q.dtype)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(bsz, s, -1)
    return proj(out, p["o"], ad["o"], scale)


def layer(p, ad, h, dm, kind):
    scale = jnp.asarray(dm.alpha / dm.rank, h.dtype)
    r = jnp.asarray(dm.res_mult, h.dtype)
    mix = mamba if kind == "mamba" else attention
    h = h + r * mix(p["mixer"], ad, rmsnorm(h, p["input_norm"]["scale"],
                                             dm.eps), dm, scale)
    m = rmsnorm(h, p["post_norm"]["scale"], dm.eps)
    g, u = jnp.split(m @ p["mlp"]["w_in"], 2, axis=-1)
    return h + r * ((jax.nn.silu(g) * u) @ p["mlp"]["w_out"])


def hidden(base, ad, tokens, dm):
    h = jnp.asarray(dm.emb_mult, base["embed"]["tokens"].dtype) * (
        base["embed"]["tokens"][tokens])
    for i, kind in enumerate(dm.layer_types):
        h = jax.checkpoint(functools.partial(layer, dm=dm, kind=kind))(
            base[layer_name(i)], ad[layer_name(i)], h)
    return rmsnorm(h, base["final_norm"]["scale"], dm.eps)


def _head_blocks(base, h, targets, dm, fn):
    """``fn(logits, targets)`` summed over blocks of HEAD_BLOCK tokens."""
    hf = h.reshape(-1, dm.d)
    tf = targets.reshape(-1)
    total = 0.0
    for i in range(0, hf.shape[0], HEAD_BLOCK):
        logits = (hf[i:i + HEAD_BLOCK] @ base["embed"]["tokens"].T
                  / jnp.asarray(dm.logits_scaling, hf.dtype))
        total = total + jax.checkpoint(fn)(logits, tf[i:i + HEAD_BLOCK])
    return total


def _nll(logits, t):
    gold = jnp.take_along_axis(logits, jnp.maximum(t, 0)[:, None], -1)[:, 0]
    return jnp.sum(jnp.where(t >= 0, jax.nn.logsumexp(logits, -1) - gold,
                             0.0))


def _hits(logits, t):
    return jnp.sum((jnp.argmax(logits, -1) == t) & (t >= 0))


def _targets(x, y):
    return jnp.where((y >= 0)[:, None], x[:, 1:], -1)


def loss(ad, base, x, y, dm):
    t = _targets(x, y)
    total = _head_blocks(base, hidden(base, ad, x[:, :-1], dm), t, dm, _nll)
    return total / jnp.maximum(jnp.sum(t >= 0), 1)


@functools.partial(jax.jit, static_argnames=("dm",))
def _local_epoch(base, ad, xb, yb, lr, dm):
    grad = jax.grad(functools.partial(loss, dm=dm))

    def step(p, batch):
        g = grad(p, base, *batch)
        return jax.tree_util.tree_map(lambda w, d: w - lr * d, p, g), None

    return jax.lax.scan(step, ad, (xb, yb))[0]


@functools.partial(jax.jit, static_argnames=("dm",))
def _accuracy(base, ad, x, y, dm):
    t = _targets(x, y)
    hits = _head_blocks(base, hidden(base, ad, x[:, :-1], dm), t, dm, _hits)
    return hits / jnp.maximum(jnp.sum(t >= 0), 1)


class GraniteLoRA:
    """The payload ``fl_horizon.run_instance`` trains: the adapters, over
    the frozen base in ``dtype``."""

    def __init__(self, config, dtype):
        self.dims = dims(config)
        self.dtype = dtype
        self.base = jax.tree_util.tree_map(lambda w: w.astype(dtype),
                                           frozen_base(self.dims))

    def init(self, key, dtype):
        return init_adapters(self.dims, key, dtype)

    def local_epoch(self, params, xb, yb, lr):
        with jax.default_matmul_precision("highest"):
            return _local_epoch(self.base, params, xb, yb, lr, self.dims)

    def accuracy(self, params, x, y):
        with jax.default_matmul_precision("highest"):
            return _accuracy(self.base, params, x, y, self.dims)


def run_instance(world, fl, cell_cfg, seed, *, dtype=jnp.float32,
                 follow=None):
    """One horizon from instance seed ``seed`` (``fl_horizon.run_instance``
    with the adapters over the frozen base of the world's configuration)."""
    return horizon.run_instance(
        world, fl, cell_cfg, seed, model=GraniteLoRA(world.config, dtype),
        dtype=dtype, follow=follow)
