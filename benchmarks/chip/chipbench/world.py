"""The benchmark's data: a MNIST-like set and its non-iid device shards,
made from ``--seed`` alone.

The image generator is a copy of the repository's synthetic MNIST stand-in
(``repro.data.mnist_like``): each class is a mixture of three Gaussian
blobs on the 28 x 28 grid plus pixel noise.  It lives here so that the
yardstick does not move when the program's own generator does.

The shards follow the Dirichlet(alpha) class-mixture protocol the program
uses, with one change: the shard *sizes* are the same for every seed.
They are the quantiles of the log-normal size law, assigned to devices in
a seeded order, so every seed gives the same padded bank and scan shapes
(the batch count of the largest shard fixes them) and a fresh seed finds
every program in the compile cache.  Which device holds which size, which
classes it holds, and every pixel change with the seed.
"""
from __future__ import annotations

import dataclasses
import statistics

import numpy as np


@dataclasses.dataclass
class Dataset:
    x_train: np.ndarray  # (N, 784) float32 in [0, 1]
    y_train: np.ndarray  # (N,) int32
    x_test: np.ndarray
    y_test: np.ndarray


@dataclasses.dataclass
class World:
    dataset: Dataset
    shards: list         # per-device index arrays into x_train
    sizes: np.ndarray    # (M,) shard sizes


def _class_prototypes(rng, num_classes, blobs):
    protos = []
    for _ in range(num_classes):
        cx = rng.uniform(5, 23, blobs)
        cy = rng.uniform(5, 23, blobs)
        sig = rng.uniform(1.5, 4.0, blobs)
        amp = rng.uniform(0.6, 1.0, blobs)
        protos.append((cx, cy, sig, amp))
    return protos


def _render(protos, rng, n):
    cx, cy, sig, amp = protos
    yy, xx = np.mgrid[0:28, 0:28]
    imgs = np.zeros((n, 28, 28), np.float32)
    for b in range(len(cx)):
        jx = cx[b] + rng.normal(0, 1.2, n)
        jy = cy[b] + rng.normal(0, 1.2, n)
        js = sig[b] * np.exp(rng.normal(0, 0.15, n))
        ja = amp[b] * np.exp(rng.normal(0, 0.2, n))
        d2 = ((xx[None] - jx[:, None, None]) ** 2
              + (yy[None] - jy[:, None, None]) ** 2)
        imgs += ja[:, None, None] * np.exp(-d2 / (2 * js[:, None, None] ** 2))
    imgs += rng.normal(0, 0.12, imgs.shape)
    return np.clip(imgs, 0.0, 1.0).reshape(n, 784).astype(np.float32)


def make_images(num_samples, train_frac, seed, num_classes=10):
    """``num_samples`` images, an equal share per class, shuffled and split."""
    rng = np.random.default_rng(seed)
    protos = _class_prototypes(rng, num_classes, blobs=3)
    per_class = num_samples // num_classes
    xs, ys = [], []
    for c in range(num_classes):
        xs.append(_render(protos[c],
                          np.random.default_rng([seed, c]), per_class))
        ys.append(np.full(per_class, c, np.int32))
    x = np.concatenate(xs)
    y = np.concatenate(ys)
    perm = rng.permutation(len(x))
    x, y = x[perm], y[perm]
    n_train = int(train_frac * len(x))
    return Dataset(x[:n_train], y[:n_train], x[n_train:], y[n_train:])


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
    """The configuration's data and shards for one ``--seed``."""
    data = config["data"]
    ds = make_images(data["num_samples"], data["train_frac"], seed)
    shards, sizes = dirichlet_shards(
        ds.y_train, config["fl"]["num_devices"], seed,
        alpha=data["alpha"], size_sigma=data["size_sigma"],
        min_per_device=data["min_per_device"],
    )
    return World(ds, shards, np.asarray(sizes))
