"""Parameter schema system: one declaration yields init + sharding specs.

A model describes its parameters as a pytree of :class:`ParamSpec` (shape +
logical axis names + initializer). From that single schema we derive:
  * ``init_params(schema, key)``  — materialized fp32 parameter pytree
  * ``logical_specs(schema)``     — same-structure pytree of logical-axis tuples,
                                    translated to PartitionSpec by repro.sharding.
  * ``abstract_params(schema)``   — ShapeDtypeStruct tree (dry-run, no allocation)

Logical axis vocabulary (resolved in repro/sharding/rules.py):
  "embed"   : d_model           -> unsharded (activations-stationary)
  "mlp"     : d_ff / heads*hd   -> tensor axis ("model")
  "heads"   : attention heads   -> tensor axis ("model")
  "kv"      : head_dim          -> unsharded
  "vocab"   : vocabulary        -> tensor axis ("model")
  "expert"  : MoE experts       -> tensor axis ("model")
  "fsdp"    : weight-shard axis -> data axis (parameter FSDP)
  "layers"  : scan-stacked layer dim -> unsharded
  None      : unsharded
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: tuple
    axes: tuple                     # logical names, len == len(shape)
    init: str = "normal"            # normal | zeros | ones | embed | ssm_a |
                                    # ssm_dt | lora_a
    scale: Optional[float] = None   # stddev override for "normal"
    dtype: str = "float32"

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _fan_in(shape) -> int:
    return int(np.prod(shape[:-1])) if len(shape) > 1 else int(shape[0])


def _materialize(spec: ParamSpec, key) -> jax.Array:
    dtype = jnp.dtype(spec.dtype)
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dtype)
    if spec.init == "ssm_a":
        # Mamba2 A init: -uniform(1, 16) stored as log for stability.
        u = jax.random.uniform(key, spec.shape, jnp.float32, 1.0, 16.0)
        return jnp.log(u).astype(dtype)
    if spec.init == "ssm_dt":
        # Mamba2 dt bias: softplus^-1 of dt ~ log-uniform on [1e-3, 1e-1]
        u = jax.random.uniform(key, spec.shape, jnp.float32)
        dt = jnp.exp(u * (jnp.log(0.1) - jnp.log(0.001)) + jnp.log(0.001))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    if spec.init == "lora_a":
        # LoRA's A (arXiv:2106.09685, as PEFT sets it): uniform within
        # +-1/sqrt(fan_in)
        bound = 1.0 / np.sqrt(max(_fan_in(spec.shape), 1))
        return jax.random.uniform(key, spec.shape, jnp.float32, -bound,
                                  bound).astype(dtype)
    if spec.init == "embed":
        std = spec.scale if spec.scale is not None else 0.02
        return (jax.random.normal(key, spec.shape, jnp.float32) * std).astype(dtype)
    # default: truncated-normal fan-in scaling
    std = spec.scale if spec.scale is not None else 1.0 / np.sqrt(max(_fan_in(spec.shape), 1))
    return (jax.random.truncated_normal(key, -3, 3, spec.shape, jnp.float32) * std).astype(dtype)


def _is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def init_params(schema, key: jax.Array):
    """Materialize a schema pytree; each leaf gets a path-derived subkey.

    The fold constant is a CRC32 of the tree path, not Python's ``hash``:
    string hashing is salted per process (PYTHONHASHSEED), which made two
    runs of the same config initialize different models — every
    "reproducible from (inputs, config) alone" claim downstream rests on
    this digest being process-independent.
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(schema, is_leaf=_is_spec)
    leaves = []
    for path, spec in flat:
        h = zlib.crc32(jax.tree_util.keystr(path).encode()) % (2**31)
        leaves.append(_materialize(spec, jax.random.fold_in(key, h)))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def logical_specs(schema):
    """Pytree of logical-axis tuples matching the parameter pytree."""
    return jax.tree_util.tree_map(lambda s: s.axes, schema, is_leaf=_is_spec)


def abstract_params(schema):
    """ShapeDtypeStruct tree for .lower() without allocation."""
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.dtype(s.dtype)),
        schema,
        is_leaf=_is_spec,
    )


def stacked(schema, n: int):
    """Prepend a scan-stacked layer dimension to every param in the subtree."""
    return jax.tree_util.tree_map(
        lambda s: dataclasses.replace(
            s, shape=(n, *s.shape), axes=("layers", *s.axes)
        ),
        schema,
        is_leaf=_is_spec,
    )
