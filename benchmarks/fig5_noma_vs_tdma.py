"""Paper Fig. 5: testing accuracy vs communication time — NOMA+compression
FedAvg vs TDMA FedAvg (both max-power, both greedily scheduled).

Paper claim to validate: the NOMA scheme reaches a given accuracy in roughly
half the wall-clock of TDMA (paper: ~70% at ~10 s vs ~22 s on real MNIST;
absolute accuracies differ on the synthetic set — see DESIGN.md §6.1)."""
from __future__ import annotations

import numpy as np

from benchmarks.common import World, build_world, emit, timeit
from repro.config import FLConfig
from repro.core import fl


def run(world: World, *, rounds: int, seed: int = 0):
    cfg = FLConfig(num_devices=world.cell.num_devices, group_size=3,
                   num_rounds=rounds, scheduler="lazy-gwmin",
                   power_mode="max", compression="adaptive", seed=seed)
    noma = fl.run_federated_learning(world.dataset, world.shards, world.cell,
                                     cfg, uplink="noma")
    tdma = fl.run_federated_learning(world.dataset, world.shards, world.cell,
                                     cfg, uplink="tdma")
    return noma, tdma


def time_to_accuracy(res, target: float):
    for log in res.logs:
        if log.test_accuracy >= target:
            return log.wall_time_s
    return np.inf


def main(fast: bool = False):
    world = build_world(num_devices=60 if fast else 150,
                        num_samples=3000 if fast else 6000)
    rounds = 8 if fast else 20
    import time as _t

    t0 = _t.perf_counter()
    noma, tdma = run(world, rounds=rounds)
    us = (_t.perf_counter() - t0) * 1e6

    acc_n, acc_t = noma.accuracies(), tdma.accuracies()
    target = 0.95 * max(acc_n.max(), acc_t.max())
    tn, tt = time_to_accuracy(noma, target), time_to_accuracy(tdma, target)
    emit("fig5.noma_final_acc", us, f"{acc_n[-1]:.3f}")
    emit("fig5.tdma_final_acc", us, f"{acc_t[-1]:.3f}")
    emit("fig5.noma_time_to_target_s", us, f"{tn:.1f}")
    emit("fig5.tdma_time_to_target_s", us, f"{tt:.1f}")
    emit("fig5.speedup", us, f"{tt / tn:.2f}" if np.isfinite(tn) else "inf")
    # paper-shape check: NOMA should reach the target no later than TDMA
    assert tn <= tt * 1.05, (tn, tt)
    return {"noma": acc_n, "tdma": acc_t, "t_noma": noma.times(),
            "t_tdma": tdma.times()}


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
