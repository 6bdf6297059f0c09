"""Model FLOPs of the work a horizon does, counted from shapes.

LeNet-300-100 is three dense layers: 784*300 + 300*100 + 100*10 =
266,200 multiply-accumulates per sample.  A training sample costs the
forward pass and the backward pass (gradients of activations and of
weights): 3 matrix products of 2 FLOPs per MAC, 6 FLOPs per MAC.  An
evaluated sample costs the forward pass, 2 FLOPs per MAC.  Only real
samples count: the all-padding batches a padded shard or a padded scan
runs are waste, and show as a lower utilization.
"""
from __future__ import annotations

import numpy as np

LENET_LAYERS = (784, 300, 100, 10)
TRAIN_FLOPS_PER_MAC = 6
EVAL_FLOPS_PER_MAC = 2


def dense_macs(widths=LENET_LAYERS):
    return sum(a * b for a, b in zip(widths[:-1], widths[1:]))


def dense_params(widths=LENET_LAYERS):
    return dense_macs(widths) + sum(widths[1:])


def horizon_flops(devices_per_round, sizes, *, epochs, test_samples,
                  widths=LENET_LAYERS):
    """FLOPs of one horizon: every scheduled client's real samples for
    ``epochs`` local epochs, and the test set after every round."""
    sizes = np.asarray(sizes, np.int64)
    macs = dense_macs(widths)
    trained = sum(int(sizes[list(devs)].sum()) for devs in devices_per_round
                  if len(devs))
    evaluated = test_samples * len(devices_per_round)
    return float(macs) * (TRAIN_FLOPS_PER_MAC * epochs * trained
                          + EVAL_FLOPS_PER_MAC * evaluated)
