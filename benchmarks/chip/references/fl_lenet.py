"""Plain reference of one FL horizon with LeNet-300-100 (arXiv:2006.13044
Sec. IV): the model's own equations, and its counts for the benchmark's
utilization.  The rest of the horizon (channels, schedule, powers,
budgets, uplink, the round loop) is the payload-free ``fl_horizon``.

Imports nothing of the program.  The widths are the configuration's
``model.widths`` (784, 300, 100, 10): dense layers, ReLU between them.

  init       truncated normal(+-3) / sqrt(fan_in) weights, zero biases
  training   minibatch SGD on the mean cross entropy of the real samples
  eval       top-1 accuracy
"""
from __future__ import annotations

import dataclasses
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.harness import BENCH_DIR, load_module

horizon = load_module(BENCH_DIR / "references" / "fl_horizon.py")

# 2 FLOPs a multiply-accumulate; training runs three products (the forward
# pass, the gradients of activations and of weights), eval the first
TRAIN_FLOPS_PER_MAC = 6
EVAL_FLOPS_PER_MAC = 2


def _widths(config):
    return tuple(config["model"]["widths"])


def _macs(widths):
    return sum(a * b for a, b in zip(widths[:-1], widths[1:]))


def sample_flops(config):
    """(train, eval) FLOPs of one real sample."""
    macs = _macs(_widths(config))
    return TRAIN_FLOPS_PER_MAC * macs, EVAL_FLOPS_PER_MAC * macs


def param_count(config):
    widths = _widths(config)
    return _macs(widths) + sum(widths[1:])


def forward(params, x):
    n = len(params)
    h = x
    for i in range(1, n + 1):
        layer = params[f"fc{i}"]
        h = h @ layer["w"] + layer["b"]
        if i < n:
            h = jax.nn.relu(h)
    return h


def masked_loss(params, x, y):
    """Mean cross entropy over the real samples of one minibatch (label -1
    marks padding, which contributes nothing)."""
    logits = forward(params, x)
    valid = (y >= 0).astype(logits.dtype)
    gold = jnp.take_along_axis(logits, jnp.maximum(y, 0)[:, None], axis=-1)
    per = (jax.nn.logsumexp(logits, axis=-1) - gold[:, 0]) * valid
    return jnp.sum(per) / jnp.maximum(jnp.sum(valid), 1.0)


@jax.jit
def local_epoch(params, xb, yb, lr):
    def step(p, batch):
        g = jax.grad(masked_loss)(p, *batch)
        return jax.tree_util.tree_map(lambda w, d: w - lr * d, p, g), None

    return jax.lax.scan(step, params, (xb, yb))[0]


@jax.jit
def accuracy(params, x, y):
    return jnp.mean((jnp.argmax(forward(params, x), axis=-1) == y)
                    .astype(jnp.float32))


@dataclasses.dataclass(frozen=True)
class LeNet:
    """The payload ``fl_horizon.run_instance`` trains."""

    widths: tuple

    def init(self, key, dtype):
        """Each weight from fold_in(key, crc32 of its path)."""
        params = {}
        for i, (fan_in, fan_out) in enumerate(zip(self.widths[:-1],
                                                  self.widths[1:])):
            name = f"fc{i + 1}"
            hw = zlib.crc32(f"['{name}']['w']".encode()) % (2**31)
            std = 1.0 / np.sqrt(fan_in)
            w = jax.random.truncated_normal(
                jax.random.fold_in(key, hw), -3, 3, (fan_in, fan_out),
                jnp.float32) * std
            params[name] = {
                "w": w.astype(dtype),
                "b": jnp.zeros((fan_out,), jnp.float32).astype(dtype)}
        return params

    local_epoch = staticmethod(local_epoch)
    accuracy = staticmethod(accuracy)


def run_instance(world, fl, cell_cfg, seed, *, dtype=jnp.float32,
                 follow=None):
    """One horizon from instance seed ``seed`` (``fl_horizon.run_instance``
    with LeNet at the widths of the world's configuration)."""
    return horizon.run_instance(
        world, fl, cell_cfg, seed, model=LeNet(_widths(world.config)),
        dtype=dtype, follow=follow)
