"""Paper Fig. 6: testing accuracy vs rounds for the four scheduling/power
schemes:
  1. optimal (MWIS) scheduling + MAPEL power allocation   (proposed)
  2. optimal scheduling + max power
  3. random scheduling + MAPEL power allocation
  4. random scheduling + max power

Paper claim: scheme 1 dominates throughout; schemes 1-3 exceed ~60% at T=35;
scheme 4 is the weakest. We validate the ORDERING (1 best, 4 worst) on the
synthetic set.

Beyond the paper's four, the sweep carries the ref [6] baselines and the
online FL-state-aware policies (update-aware: Amiri et al. arXiv:2001.10402;
age-fair: Yang et al. arXiv:1908.06287; matching-pursuit: the OTA companion
policy of repro.core.ota, which at ota_noise=0 greedily admits by weighted
update energy), all running live inside the training loop — every curve
goes through the same policy registry."""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import World, build_world, emit
from repro.config import FLConfig
from repro.core import fl

SCHEMES = [
    ("opt_sched+opt_power", "lazy-gwmin", "mapel"),
    ("opt_sched+max_power", "lazy-gwmin", "max"),
    ("rand_sched+opt_power", "random", "mapel"),
    ("rand_sched+max_power", "random", "max"),
    # ref [6] policies for context (beyond the paper's four)
    ("round_robin+max_power", "round-robin", "max"),
    ("prop_fair+max_power", "proportional-fair", "max"),
    # online FL-state-aware policies (live select_round inside the FL loop)
    ("update_aware+max_power", "update-aware", "max"),
    ("age_fair+max_power", "age-fair", "max"),
    ("matching_pursuit+max_power", "matching-pursuit", "max"),
]


def main(fast: bool = False):
    world = build_world(num_devices=60 if fast else 150,
                        num_samples=3000 if fast else 6000)
    rounds = 8 if fast else 20
    finals = {}
    curves = {}
    t0 = time.perf_counter()
    for name, sched, power in SCHEMES:
        cfg = FLConfig(num_devices=world.cell.num_devices, group_size=3,
                       num_rounds=rounds, scheduler=sched, power_mode=power,
                       compression="adaptive", seed=0)
        res = fl.run_federated_learning(world.dataset, world.shards,
                                        world.cell, cfg, uplink="noma")
        finals[name] = res.accuracies()[-1]
        curves[name] = res.accuracies()
    us = (time.perf_counter() - t0) * 1e6
    for name, acc in finals.items():
        emit(f"fig6.{name}", us / len(SCHEMES), f"{acc:.3f}")
    # mean-over-rounds captures "consistently best" better than the endpoint
    means = {k: float(np.mean(v)) for k, v in curves.items()}
    emit("fig6.proposed_mean_acc", us / len(SCHEMES),
         f"{means['opt_sched+opt_power']:.3f}")
    best = max(means, key=means.get)
    emit("fig6.best_scheme", us / len(SCHEMES), best)
    return curves


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
