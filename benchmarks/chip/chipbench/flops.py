"""Model FLOPs of the work a horizon does, counted from shapes.

A configuration's reference gives the FLOPs of one real sample
(``sample_flops(config) -> (train, eval)``): a trained sample costs the
forward and the backward pass, an evaluated sample the forward pass.
Only real samples count: the all-padding batches a padded shard or a
padded scan runs are waste, and show as a lower utilization.
"""
from __future__ import annotations

import numpy as np


def horizon_flops(devices_per_round, sizes, *, epochs, test_samples,
                  train_flops, eval_flops):
    """FLOPs of one horizon: every scheduled client's real samples for
    ``epochs`` local epochs, and the test set after every round."""
    sizes = np.asarray(sizes, np.int64)
    trained = sum(int(sizes[list(devs)].sum()) for devs in devices_per_round
                  if len(devs))
    evaluated = test_samples * len(devices_per_round)
    return float(train_flops * epochs * trained + eval_flops * evaluated)
