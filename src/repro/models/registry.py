"""Model registry: one uniform Model facade per architecture family."""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax

from repro.config import ModelConfig
from repro.models import (encdec, hybrid, mamba2, moe, pattern_hybrid,
                          transformer, vlm)
from repro.models.params import abstract_params, init_params, logical_specs

_FAMILIES = {
    "dense": transformer,
    "moe": moe,
    "ssm": mamba2,
    "hybrid": hybrid,
    "pattern": pattern_hybrid,
    "encdec": encdec,
    "vlm": vlm,
}


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    schema: Any
    module: Any
    shards: int

    # ---- params ----
    def init(self, key: jax.Array):
        return init_params(self.schema, key)

    def abstract(self):
        return abstract_params(self.schema)

    def param_logical_specs(self):
        return logical_specs(self.schema)

    # ---- compute ----
    def loss(self, params, batch, **kw):
        return self.module.loss_fn(params, batch, self.cfg, **kw)

    def forward(self, params, batch, **kw):
        extra = _modal_kwargs(self.cfg, batch)
        return self.module.forward(params, batch["tokens"], self.cfg, **extra, **kw)

    def init_cache(self, batch: int, max_len: int):
        return self.module.init_cache(self.cfg, batch, max_len, shards=self.shards)

    def decode_step(self, params, caches, tokens, *, batch=None, **kw):
        extra = _modal_kwargs(self.cfg, batch or {}, decode=True)
        return self.module.decode_step(params, caches, tokens, self.cfg, **extra, **kw)


def _modal_kwargs(cfg, batch, *, decode: bool = False):
    out = {}
    if cfg.family == "vlm":
        out["img_feats"] = batch["img_feats"]
    if cfg.family == "encdec":
        if decode:
            out["enc_out"] = batch["enc_out"]
        else:
            out["enc_feats"] = batch["enc_feats"]
    return out


def build_model(cfg: ModelConfig, *, shards: int = 1) -> Model:
    if cfg.family not in _FAMILIES:
        raise KeyError(f"unknown family {cfg.family!r}")
    module = _FAMILIES[cfg.family]
    return Model(cfg, module.schema(cfg, shards=shards), module, shards)
