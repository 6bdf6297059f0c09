"""Quickstart: the paper's pipeline end-to-end in ~1 minute on CPU.

    PYTHONPATH=src python examples/quickstart.py

Builds a small NOMA cell, schedules devices with the MWIS greedy, allocates
power with MAPEL, runs a few FedAvg rounds with adaptive DoReFa compression,
and prints the accuracy trajectory.
"""
import numpy as np

from repro.config import FLConfig
from repro.core import channel, fl
from repro.data import dirichlet_partition, make_mnist_like
from repro.utils.compile_cache import enable_compile_cache

enable_compile_cache()

M, K, T = 30, 3, 6

print("== 1. world: synthetic MNIST-like dataset, non-iid across", M, "devices")
ds = make_mnist_like(num_samples=2500, seed=0)
cell = channel.CellConfig(num_devices=M)
shards = dirichlet_partition(ds.y_train, M, seed=0)
print(f"   train={len(ds.x_train)} test={len(ds.x_test)} "
      f"device sizes: min={min(map(len, shards))} max={max(map(len, shards))}")

print(f"== 2. FL over NOMA: MWIS scheduling + MAPEL power, K={K}, T={T}")
cfg = FLConfig(num_devices=M, group_size=K, num_rounds=T,
               scheduler="lazy-gwmin", power_mode="mapel",
               compression="adaptive", seed=0)
res = fl.run_federated_learning(
    ds, shards, cell, cfg, uplink="noma",
    progress=lambda log: print(
        f"   round {log.round}: devices={log.devices} "
        f"rates={np.round(log.rates, 2)} bits={log.bits} "
        f"acc={log.test_accuracy:.3f} t={log.wall_time_s:.1f}s"))

print(f"== 3. final accuracy {res.accuracies()[-1]:.3f} "
      f"(scheme {res.scheme})")
assert res.accuracies()[-1] > res.accuracies()[0]
