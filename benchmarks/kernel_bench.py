"""Gradient-codec kernel microbenchmarks (paper §II-B compute hot-spot).

On this CPU container the Pallas path runs in interpret mode (Python), so
the jnp/XLA path is the production-CPU number; the interpret number only
validates the kernel wiring. On TPU the pallas_call path is the deployed
one."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, timeit
from repro.core import rates
from repro.kernels import ops

N = 1_048_576  # ~1M params (4 MiB f32), LeNet-scale x4


def main(fast: bool = False):
    n = N // 8 if fast else N
    x = jax.random.normal(jax.random.PRNGKey(0), (n,))

    for bits in (1, 8):
        out = ops.quantize_dequantize(x, bits)  # compile
        us = timeit(lambda: ops.quantize_dequantize(x, bits).block_until_ready())
        emit(f"kernel.qdq_b{bits}_xla", us, f"{n} elems")

    codes, scale = ops.quantize_pack(x, 8)
    us = timeit(lambda: ops.quantize_pack(x, 8)[0].block_until_ready())
    emit("kernel.quantize_pack_xla", us, f"{n} elems")
    us = timeit(
        lambda: ops.unpack_dequantize(codes, scale, 8, n).block_until_ready())
    emit("kernel.dequantize_xla", us, f"{n} elems")

    k = 3
    stack = jnp.stack([codes] * k)
    scales = jnp.full((k,), float(scale))
    w = jnp.full((k,), 1.0 / k)
    out = ops.weighted_aggregate(stack, scales, w, 8)
    us = timeit(
        lambda: ops.weighted_aggregate(stack, scales, w, 8).block_until_ready())
    emit("kernel.aggregate_k3_xla", us, f"{n} elems")

    # pallas interpret (validation path; slow by construction on CPU)
    small = x[: 131_072]
    out = ops.quantize_dequantize(small, 8, use_pallas=True)
    us = timeit(
        lambda: ops.quantize_dequantize(small, 8, use_pallas=True)
        .block_until_ready(), repeats=1)
    emit("kernel.qdq_b8_pallas_interpret", us, f"{small.size} elems")

    # batched SIC group scoring (scheduler candidate batches, K=3)
    v = 8_192 if fast else 65_536
    rng = np.random.default_rng(0)
    g_vk = np.abs(rng.normal(1e-6, 5e-7, (v, 3))) + 1e-8
    p_vk = np.full((v, 3), 0.01)
    w_vk = rng.dirichlet(np.ones(3), size=v)
    noise = 1.6e-14
    us = timeit(lambda: rates.batched_weighted_rates(p_vk, g_vk, w_vk, noise))
    emit("kernel.sic_rates_numpy", us, f"{v} groups")
    pj, gj, wj = jnp.asarray(p_vk), jnp.asarray(g_vk), jnp.asarray(w_vk)
    out = ops.sic_weighted_rates(pj, gj, wj, noise)  # compile
    us = timeit(
        lambda: ops.sic_weighted_rates(pj, gj, wj, noise).block_until_ready())
    emit("kernel.sic_rates_xla", us, f"{v} groups")
    vp = 2_048
    out = ops.sic_weighted_rates(
        pj[:vp], gj[:vp], wj[:vp], noise, use_pallas=True)
    us = timeit(
        lambda: ops.sic_weighted_rates(
            pj[:vp], gj[:vp], wj[:vp], noise, use_pallas=True
        ).block_until_ready(), repeats=1)
    emit("kernel.sic_rates_pallas_interpret", us, f"{vp} groups")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
