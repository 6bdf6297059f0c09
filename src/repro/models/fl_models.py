"""FL model facade + registry: the payload the NOMA uplink actually moves.

The paper's scheduling/power machinery exists to move *model updates* over a
bandwidth-limited uplink, but the FL stack historically hardcoded
LeNet-on-MNIST in the round body.  This module is the seam that removes
that: an :class:`FLModel` is the small, hashable facade the FL engine
(``repro.core.fl_engine``) and driver (``repro.core.fl``) consume —

  * ``schema()`` / ``init(key)``   — the parameter pytree (the payload)
  * ``batch_loss(params, bx, by, valid)`` — masked mean loss of ONE
    minibatch; ``by`` uses the bank's -1-is-padding convention and
    ``valid = (by >= 0)`` as float32 is precomputed by the shared SGD epoch
  * ``accuracy(params, x, y)``     — test metric for the eval banks
  * ``kind``                        — "image" (flat (N, D) float features +
    (N,) labels) or "tokens" ((N, S) int32 token rows + (N, S) next-token
    labels, see :func:`repro.data.tokens.make_token_dataset`)

``FLConfig.model`` resolves here through :func:`get_fl_model`.  The default
``"lenet"`` adapter reproduces the historical round body bit for bit (same
forward, same masked-loss ops, same ``lenet.accuracy`` eval).  Token models
wrap the :mod:`repro.models.registry` family modules (dense / moe / ssm /
hybrid) with a masked next-token cross-entropy, so any registry config —
including the full ``repro.configs`` architecture zoo — trains through the
identical batched engine / scanned horizon.  Names:

  * ``"lenet"``                — the paper's LeNet-300-100 (image kind)
  * ``"tiny-transformer"``     — 2-layer d=32 dense transformer (tests)
  * ``"tiny-transformer-1m"``  — >=10^6-param dense transformer (the
    transformer-class payload the compression stack is pinned on)
  * ``"<arch_id>"`` / ``"<arch_id>:smoke"`` — any ``repro.configs`` id
    (e.g. ``qwen2_0_5b``), resolved lazily to its CONFIG / SMOKE variant.
  * ``"granite-h-micro-lora"`` — federated LoRA over a frozen base of one
    granite-4.0-h-micro period at published widths (:class:`LoRAFLModel`,
    kind ``"token_rows"``); ``"granite-h-micro-lora:smoke"`` the same over
    the reduced SMOKE base (tests).

A payload over a frozen base (``frozen_base()``) uploads its adapters
only: ``init`` and the engine's parameters are the adapters, and the
engine hands the loss and the metric the pair ``(base, adapters)``
(``fl_engine.bound``), the base a traced operand it never differentiates,
carries, vmaps or sends.

FLModel instances are frozen dataclasses (hashable), so they ride through
``jax.jit`` static args and the sharded-horizon ``lru_cache`` unchanged.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.configs import granite_4_0_h_micro as granite
from repro.models import lenet


@dataclasses.dataclass(frozen=True)
class LenetFLModel:
    """The paper's own model: bit-compatible adapter over repro.models.lenet.

    ``batch_loss`` is the exact op sequence the pre-registry engine inlined
    (forward -> logsumexp -> take_along_axis gold -> valid-masked mean), so
    ``FLConfig(model="lenet")`` traces the identical jaxpr and the legacy
    equality grids keep their historical values.
    """

    name: str = "lenet"
    kind: str = "image"

    def schema(self):
        return lenet.schema()

    def init(self, key: jax.Array):
        from repro.models.params import init_params

        return init_params(lenet.schema(), key)

    def batch_loss(self, params, bx, by, valid):
        logits = lenet.forward(params, bx)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, by[:, None], axis=-1)[:, 0]
        per = (logz - gold) * valid
        return jnp.sum(per) / jnp.maximum(jnp.sum(valid), 1.0)

    def accuracy(self, params, x, y):
        return lenet.accuracy(params, x, y)


# Families whose ``forward(params, tokens, cfg)`` needs no extra modality
# kwargs — the FL uplink path trains language-model-shaped payloads; vlm /
# encdec need per-batch image/encoder features the ClientBank doesn't carry.
_TOKEN_FAMILIES = ("dense", "moe", "ssm", "hybrid", "pattern")


@dataclasses.dataclass(frozen=True)
class TokenFLModel:
    """Next-token-prediction adapter over a registry family module.

    Shards are (n, S) int32 token rows with (n, S) shifted labels
    (:func:`repro.data.tokens.make_token_dataset`); the bank pads with
    label -1, which :func:`repro.models.layers.cross_entropy` masks, so an
    all-padding batch contributes an exactly-zero gradient — the same
    convention the image path enforces through ``valid``.
    """

    cfg: ModelConfig
    name: str
    kind: str = "tokens"

    def __post_init__(self):
        if self.cfg.family not in _TOKEN_FAMILIES:
            raise ValueError(
                f"FL token models support families {_TOKEN_FAMILIES}, got "
                f"{self.cfg.family!r} ({self.cfg.name}): vlm/encdec forwards "
                f"need modality features the client bank does not carry"
            )

    def _module(self):
        from repro.models.registry import _FAMILIES

        return _FAMILIES[self.cfg.family]

    def schema(self):
        # shards=1: FL clients hold (and upload) the whole replica — the
        # uplink is the bottleneck being studied, not tensor parallelism.
        return self._module().schema(self.cfg, shards=1)

    def init(self, key: jax.Array):
        from repro.models.params import init_params

        return init_params(self.schema(), key)

    def batch_loss(self, params, bx, by, valid):
        from repro.models import layers as L

        del valid  # cross_entropy masks by < 0 itself (identical mask)
        logits, _ = self._module().forward(params, bx, self.cfg)
        return L.cross_entropy(logits, by, vocab_size=self.cfg.vocab_size)

    def accuracy(self, params, x, y):
        """Next-token top-1 accuracy over non-padding positions."""
        logits, _ = self._module().forward(params, x, self.cfg)
        pred = jnp.argmax(logits[..., : self.cfg.vocab_size], axis=-1)
        mask = (y >= 0).astype(jnp.float32)
        hit = (pred == jnp.maximum(y, 0)).astype(jnp.float32) * mask
        return jnp.sum(hit) / jnp.maximum(jnp.sum(mask), 1.0)


BASE_SEED = 20251002
# PRNGKey(BASE_SEED) is the root of every frozen base: a fixed function of
# the model, as a released checkpoint is, and of no instance seed

_BASES: dict = {}
# frozen bases built in this process, keyed by the model config: built once on
# the device and shared by every horizon and every call after


@dataclasses.dataclass(frozen=True)
class LoRAFLModel:
    """Federated LoRA (arXiv:2106.09685; FedIT, arXiv:2305.05644) over a
    frozen base of a :mod:`repro.models.pattern_hybrid` model.

    Kind ``"token_rows"``: a sample is a row of S + 1 token ids and its
    label one int per row (any value >= 0 trains; -1 marks a padding row);
    the loss is the mean next-token NLL over the S positions of the real
    rows, the metric the next-token top-1 accuracy.  Both read the head in
    blocks of ``block`` tokens.  The adapters are ``rank`` r with scale
    ``alpha / r``.

    The base is ``init_params(pattern_hybrid.schema(cfg),
    PRNGKey(BASE_SEED))``: leaf at path p from fold_in(key, crc32(p)),
    the initializers of ``repro.models.params`` (matrices truncated normal
    +-3 over sqrt(fan_in), the embedding normal x 0.02, norms and D ones,
    conv bias zeros, A_log log U(1, 16), dt_bias softplus^-1 of dt
    log-uniform on [1e-3, 1e-1]).  The adapters are
    ``init_params(pattern_hybrid.lora_schema(cfg, rank), key)`` of the
    instance key.
    """

    cfg: ModelConfig
    name: str
    rank: int = 8
    alpha: float = 16.0
    block: int = 512
    kind: str = "token_rows"

    def schema(self):
        from repro.models import pattern_hybrid

        return pattern_hybrid.lora_schema(self.cfg, self.rank)

    def init(self, key: jax.Array):
        from repro.models.params import init_params

        return init_params(self.schema(), key)

    def frozen_base(self):
        """The base on the device, built the first time a process asks."""
        from repro.utils import spans

        with spans.span("fl.base"):
            base = _BASES.get(self.cfg)
            if base is None:
                base = _build_base(self.cfg)
                _BASES[self.cfg] = base
                spans.count("payload.base_bytes_built", sum(
                    x.nbytes for x in jax.tree_util.tree_leaves(base)))
            return base

    def _hidden(self, params, tokens):
        from repro.models import pattern_hybrid

        base, lora = params
        return pattern_hybrid.hidden(base, tokens, self.cfg, lora=lora,
                                     lora_scale=self.alpha / self.rank)

    def _labels(self, x, real):
        """Next-token targets of each row, -1 on the rows not ``real``."""
        return jnp.where(real[:, None], x[:, 1:], -1)

    def batch_loss(self, params, bx, by, valid):
        from repro.models import pattern_hybrid

        labels = self._labels(bx, valid > 0)
        total = pattern_hybrid.token_nll(
            params[0], self._hidden(params, bx[:, :-1]), labels, self.cfg,
            block=self.block)
        count = jnp.sum(labels >= 0).astype(jnp.float32)
        return total / jnp.maximum(count, 1.0)

    def accuracy(self, params, x, y):
        from repro.models import pattern_hybrid

        labels = self._labels(x, y >= 0)
        hits = pattern_hybrid.token_hits(
            params[0], self._hidden(params, x[:, :-1]), labels, self.cfg,
            block=self.block)
        count = jnp.sum(labels >= 0).astype(jnp.float32)
        return hits.astype(jnp.float32) / jnp.maximum(count, 1.0)


def _build_base(cfg):
    from repro.models import pattern_hybrid
    from repro.models.params import init_params

    schema = pattern_hybrid.schema(cfg)
    return jax.jit(functools.partial(init_params, schema))(
        jax.random.PRNGKey(BASE_SEED))


def frozen_base(model):
    """``model``'s frozen base on the device, or None for a payload that
    trains all its parameters."""
    get = getattr(model, "frozen_base", None)
    return None if get is None else get()


TINY_TRANSFORMER = ModelConfig(
    name="fl-tiny-transformer", family="dense",
    num_layers=2, d_model=32, num_heads=2, num_kv_heads=2,
    d_ff=64, vocab_size=64, head_dim=16, tie_embeddings=True,
    source="FL engine x model equality grid (tests)",
)

TINY_TRANSFORMER_1M = ModelConfig(
    name="fl-tiny-transformer-1m", family="dense",
    num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
    d_ff=128, vocab_size=16_384, head_dim=16, tie_embeddings=True,
    source="transformer-class (>=1e6 param) FL payload pin",
)


_REGISTRY: dict = {}


def register_fl_model(name: str, factory: Callable[[], object]) -> None:
    """Register a named FLModel factory (idempotent re-registration)."""
    _REGISTRY[name] = factory


register_fl_model("lenet", LenetFLModel)
register_fl_model(
    "tiny-transformer",
    lambda: TokenFLModel(cfg=TINY_TRANSFORMER, name="tiny-transformer"),
)
register_fl_model(
    "tiny-transformer-1m",
    lambda: TokenFLModel(cfg=TINY_TRANSFORMER_1M, name="tiny-transformer-1m"),
)


register_fl_model(
    "granite-h-micro-lora",
    lambda: LoRAFLModel(cfg=granite.PERIOD, name="granite-h-micro-lora"),
)
register_fl_model(
    "granite-h-micro-lora:smoke",
    lambda: LoRAFLModel(cfg=granite.SMOKE, name="granite-h-micro-lora:smoke",
                        block=32),
)


def available_fl_models() -> tuple:
    """Registered names (the ``repro.configs`` arch-id fallback is open)."""
    return tuple(sorted(_REGISTRY))


@functools.lru_cache(maxsize=None)
def get_fl_model(name: str):
    """Resolve ``FLConfig.model`` to an FLModel.

    Explicit registrations win; otherwise ``name`` (or ``name:smoke``) is
    resolved through the :mod:`repro.configs` architecture registry, so the
    whole config zoo is reachable without per-arch boilerplate.  Raises
    ``ValueError`` on unknown names (FLConfig validation surfaces this at
    construction time).
    """
    if name in _REGISTRY:
        return _REGISTRY[name]()
    base, _, variant = name.partition(":")
    if variant not in ("", "smoke"):
        raise ValueError(
            f"unknown FL model variant {variant!r} in {name!r}; "
            f"use '<arch_id>' or '<arch_id>:smoke'"
        )
    try:
        from repro.configs import get_config, get_smoke

        cfg = get_smoke(base) if variant == "smoke" else get_config(base)
    except ImportError:
        raise ValueError(
            f"unknown FL model {name!r}; registered: "
            f"{available_fl_models()}, plus any repro.configs arch id "
            f"('<arch_id>' or '<arch_id>:smoke')"
        ) from None
    return TokenFLModel(cfg=cfg, name=name)
