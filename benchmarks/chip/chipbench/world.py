"""The benchmark's data: a world of one kind, made from ``--seed`` alone.

A configuration's ``data.kind`` (``images`` where it names none) picks the
generator ``worlds/<kind>.py``, whose ``build(config, seed)`` returns a
``World``: the training and test set and the devices' shards.  Every kind
shards its training set by the one law here.

The shards follow the Dirichlet(alpha) class-mixture protocol the program
uses, with one change: the shard *sizes* are the same for every seed.
They are the quantiles of the log-normal size law, assigned to devices in
a seeded order, so every seed gives the same padded bank and scan shapes
(the batch count of the largest shard fixes them) and a fresh seed finds
every program in the compile cache.  Which device holds which size, which
classes it holds, and every sample change with the seed.
"""
from __future__ import annotations

import dataclasses
import statistics

import numpy as np


@dataclasses.dataclass
class Dataset:
    x_train: np.ndarray  # (N, ...) one row a sample, in the kind's layout
    y_train: np.ndarray  # (N, ...) int32 labels
    x_test: np.ndarray
    y_test: np.ndarray


@dataclasses.dataclass
class World:
    dataset: Dataset
    shards: list         # per-device index arrays into x_train
    sizes: np.ndarray    # (M,) shard sizes
    config: dict = dataclasses.field(default=None, repr=False)
    # the configuration the world was built for, so that a reference can
    # read its model's sizes from it


def shard_sizes(total, num_devices, size_sigma, min_per_device):
    """Seed-free shard sizes: quantile i of LogNormal(0, sigma) for each of
    the M devices, scaled to ``total``, floored, the remainder handed one
    sample each to the largest shards."""
    norm = statistics.NormalDist()
    z = np.array([norm.inv_cdf((i + 0.5) / num_devices)
                  for i in range(num_devices)])
    raw = np.exp(size_sigma * z)
    sizes = np.maximum((raw / raw.sum() * total).astype(int), min_per_device)
    extra = total - int(sizes.sum())
    if extra < 0 or extra > num_devices:
        raise ValueError(f"shard sizes cannot sum to {total}")
    sizes[num_devices - extra:] += 1
    return sizes


def dirichlet_shards(labels, num_devices, seed, *, alpha, size_sigma,
                     min_per_device):
    """Every training sample to exactly one device.  Device d holds
    ``sizes[perm[d]]`` samples whose classes follow its own
    Dirichlet(alpha) mixture; a class that runs out is replaced by the
    class with the most samples left."""
    rng = np.random.default_rng([seed, 1])
    num_classes = int(labels.max()) + 1
    sizes = shard_sizes(len(labels), num_devices, size_sigma,
                        min_per_device)[rng.permutation(num_devices)]
    pools = [list(rng.permutation(np.flatnonzero(labels == c)))
             for c in range(num_classes)]
    mixes = rng.dirichlet(np.full(num_classes, alpha), num_devices)
    shards = []
    for d in range(num_devices):
        want = rng.choice(num_classes, size=int(sizes[d]), p=mixes[d])
        take = []
        for c in want:
            if not pools[c]:
                c = int(np.argmax([len(p) for p in pools]))
            take.append(pools[c].pop())
        shards.append(np.asarray(rng.permutation(take), np.int64))
    return shards, sizes


def build_world(config, seed):
    """The configuration's data and shards for one ``--seed``, from the
    generator of its ``data.kind``."""
    from chipbench import harness

    kind = config["data"].get("kind", "images")
    path = harness.BENCH_DIR / "worlds" / f"{kind}.py"
    if not path.is_file():
        raise KeyError(f"no world kind {kind!r}: {path} is missing")
    world = harness.load_module(path).build(config, seed)
    world.config = config
    return world
