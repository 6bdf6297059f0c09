"""MAPEL power allocation quality/latency vs grid oracle and max-power
baseline (paper §III-C / ref [8])."""
from __future__ import annotations

import numpy as np

from benchmarks.common import emit, timeit
from repro.core import power

NOISE = 1.6e-14
PMAX = 0.01


def main(fast: bool = False):
    rng = np.random.default_rng(0)
    n = 5 if fast else 10
    ratios_grid, ratios_max, times = [], [], []
    for seed in range(n):
        r = np.random.default_rng(seed)
        gains = np.abs(r.normal(1e-6, 5e-7, 3)) + 1e-8
        w = r.dirichlet(np.ones(3))
        us = timeit(lambda: power.mapel(gains, w, PMAX, NOISE), repeats=1)
        times.append(us)
        sol = power.mapel(gains, w, PMAX, NOISE)
        grid = power.grid_oracle(gains, w, PMAX, NOISE, points=15)
        maxp = power.weighted_rate(power.max_power(gains, PMAX), gains, w, NOISE)
        ratios_grid.append(sol.weighted_rate / grid.weighted_rate)
        ratios_max.append(sol.weighted_rate / max(maxp, 1e-12))
    emit("power.mapel_us", float(np.median(times)),
         f"vs_grid {np.mean(ratios_grid):.4f}")
    emit("power.mapel_vs_maxpower", float(np.median(times)),
         f"gain {np.mean(ratios_max):.4f}x")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
