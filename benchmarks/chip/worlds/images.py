"""World kind ``images``: a MNIST-like set of 28 x 28 images in ten
classes, and its non-iid device shards, made from ``--seed`` alone.

The image generator is a copy of the repository's synthetic MNIST stand-in
(``repro.data.mnist_like``): each class is a mixture of three Gaussian
blobs on the 28 x 28 grid plus pixel noise.  It lives here so that the
yardstick does not move when the program's own generator does.
"""
from __future__ import annotations

import numpy as np

from chipbench.world import Dataset, World, dirichlet_shards


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


def build(config, seed):
    """The configuration's images and shards for one ``--seed``."""
    data = config["data"]
    ds = make_images(data["num_samples"], data["train_frac"], seed)
    shards, sizes = dirichlet_shards(
        ds.y_train, config["fl"]["num_devices"], seed,
        alpha=data["alpha"], size_sigma=data["size_sigma"],
        min_per_device=data["min_per_device"],
    )
    return World(ds, shards, np.asarray(sizes))
