"""Paper §II-B: adaptive compression statistics over a simulated horizon —
average compression ratio, bit-width distribution, and quantization error
vs rate."""
from __future__ import annotations

import time

import jax
import numpy as np

from benchmarks.common import build_world, emit
from repro.config import FLConfig
from repro.core import channel, fl, noma
from repro.core import quantization as q


def main(fast: bool = False):
    world = build_world(num_devices=60, num_samples=2000)
    rounds = 6 if fast else 12
    cfg = FLConfig(num_devices=60, group_size=3, num_rounds=rounds,
                   scheduler="lazy-gwmin", power_mode="max")
    t0 = time.perf_counter()
    res = fl.run_federated_learning(world.dataset, world.shards, world.cell,
                                    cfg, uplink="noma")
    us = (time.perf_counter() - t0) * 1e6
    bits = np.concatenate([l.bits for l in res.logs])
    ratios = np.concatenate([l.compression_ratios for l in res.logs])
    emit("compress.mean_bits", us, f"{bits.mean():.2f}")
    emit("compress.mean_ratio", us, f"{ratios.mean():.1f}x")
    emit("compress.min_bits", us, str(int(bits.min())))

    # error vs bits curve (static)
    x = jax.random.normal(jax.random.PRNGKey(0), (100_000,)) * 0.1
    errs = {b: float(q.quantization_error(x, b)) for b in (1, 2, 4, 8, 16)}
    emit("compress.rmse_curve", 0.0,
         " ".join(f"b{b}={e:.2e}" for b, e in errs.items()))


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
