"""Large-payload aggregation benchmark: chunked Pallas kernel vs XLA einsum.

The batched FL engine reduces K quantized client updates into one weighted
sum per round — at LeNet scale that is a (K, 266,610) einsum, but the
model-agnostic payload path (``FLConfig.model``) moves transformer-class
update vectors (10^6-10^8 params, qwen2_0_5b is ~4.9e8).  This bench
measures the two aggregation backends the engine can take at those sizes:

  * ``einsum``  — the XLA default (``jnp.einsum("k,kn->n", coeff, codes)``
    with the dequant scales folded into the coefficients), and
  * ``pallas``  — :func:`repro.kernels.aggregate.weighted_aggregate_pallas`,
    which now chunks the parameter axis (``lax.map`` over (K, chunk_elems)
    slabs) so the padded tile grid for the whole payload is never resident
    at once.

Payload sizes are anchored on the FL model zoo: the smallest point is the
``tiny-transformer-1m`` payload the compression stack is pinned on, and
every record carries ``qwen2_frac`` — the fraction of the full qwen2-0.5B
parameter count (schema-derived, nothing materialized) the point covers.
On this CPU the Pallas path runs in interpret mode and loses to the einsum
by design (see ROADMAP: Mosaic-on-TPU is where the kernel is meant to
win); the bench records both so the crossover is visible the day the
hardware changes.  ``benchmarks/run.py`` persists the records to
``BENCH_payload.json`` (``BENCH_payload_fast.json`` under --fast/--smoke)
and gates both medians under ``--check-regression``.
"""
from __future__ import annotations

import functools
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.kernels.aggregate import DEFAULT_CHUNK_ELEMS, weighted_aggregate_pallas

K = 4          # clients per round (paper-scale group sizes are 2-16)
BITS = 8       # mid-range adaptive width; levels a = 2^b - 1 per client

# tiny-transformer-1m payload (the >=1e6-param FL pin) up to 2^25 params —
# ~6.8% of qwen2-0.5B, the largest slab the interpret-mode kernel sweeps in
# reasonable single-core time (the math is size-linear beyond the chunk).
FULL_SIZES = (1_122_624, 8_388_608, 33_554_432)
FAST_SIZES = (262_144, 1_122_624)


def _qwen2_params() -> int:
    """Full qwen2-0.5B parameter count, derived from the schema only."""
    from repro.configs import get_config
    from repro.models.registry import _FAMILIES

    cfg = get_config("qwen2_0_5b")
    schema = _FAMILIES[cfg.family].schema(cfg, shards=1)
    return sum(
        int(np.prod(leaf.shape))
        for leaf in jax.tree_util.tree_leaves(
            schema, is_leaf=lambda x: hasattr(x, "shape")
        )
    )


# module-level jitted backends (flcheck FLC001): a jit(lambda) built inside
# the size loop is a fresh function object per size, so every call misses
# the jit cache and the benchmark times retracing, not the kernel
@jax.jit
def _einsum_aggregate(codes, coeff):
    return jnp.einsum("k,kn->n", coeff, codes)


@jax.jit
def _pallas_aggregate(codes, scales, weights, levels):
    return weighted_aggregate_pallas(codes, scales, weights, levels=levels)


def _best_seconds(fn, arg, *, passes: int) -> float:
    """Warm-compile once, then best-of-``passes`` wall seconds."""
    fn(arg).block_until_ready()
    best = np.inf
    for _ in range(passes):
        t0 = time.perf_counter()
        fn(arg).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return float(best)


def main(fast: bool = False) -> dict:
    sizes = FAST_SIZES if fast else FULL_SIZES
    qwen2 = _qwen2_params()
    rng = np.random.default_rng(0)
    scales = jnp.asarray(rng.uniform(0.5, 2.0, K).astype(np.float32))
    levels = jnp.asarray(np.full(K, float(2**BITS - 1), np.float32))
    weights = jnp.asarray(rng.uniform(0.1, 1.0, K).astype(np.float32))
    coeff = weights * scales / levels

    records = []
    for p in sizes:
        gc.collect()   # drop the previous size's (K, P) block now
        codes = jnp.asarray(
            rng.integers(-(2**BITS - 1), 2**BITS, (K, p)).astype(np.float32)
        )
        einsum_fn = functools.partial(_einsum_aggregate, coeff=coeff)
        pallas_fn = functools.partial(
            _pallas_aggregate, scales=scales, weights=weights, levels=levels
        )
        passes = 3 if p <= 2**23 else 2
        einsum_s = _best_seconds(einsum_fn, codes, passes=passes)
        pallas_s = _best_seconds(pallas_fn, codes, passes=passes)
        # the two backends must agree (chunk boundaries don't touch the
        # math); a bench that silently diverged would be worthless
        diff = float(jnp.max(jnp.abs(einsum_fn(codes) - pallas_fn(codes))))
        assert diff < 1e-5 * float(p) ** 0.5, f"backends diverge: {diff}"
        chunks = -(-p // DEFAULT_CHUNK_ELEMS)
        records.append({
            "params": int(p), "k": K, "bits": BITS, "chunks": int(chunks),
            "qwen2_frac": round(p / qwen2, 4),
            "einsum_s": einsum_s,
            "pallas_chunked_s": pallas_s,
            # "speedup" prefix: excluded from the --check-regression
            # record identity key (a derived ratio, tracked not gated)
            "speedup_einsum_over_pallas": round(pallas_s / einsum_s, 2),
        })
        emit(f"payload.einsum_P{p}_K{K}", einsum_s * 1e6)
        emit(f"payload.pallas_chunked_P{p}_K{K}", pallas_s * 1e6,
             f"einsum {pallas_s / einsum_s:.1f}x faster (CPU interpret)")
        del codes
    return {
        "suite": "payload_aggregation",
        "settings": {
            "k": K, "bits": BITS, "chunk_elems": int(DEFAULT_CHUNK_ELEMS),
            "qwen2_0_5b_params": int(qwen2),
            "backend": jax.default_backend(),
            "pallas_mode": "interpret (CPU)",
        },
        "records": records,
    }


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
