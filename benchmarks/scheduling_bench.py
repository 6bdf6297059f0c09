"""Algorithm 2 scaling: literal graph vs lazy column generation, the batched
SIC rate engine vs the seed's per-subset Python loop, the greedy's optimality
gap vs brute force (paper §III), and the numpy-vs-jax backend sweep whose
records ``benchmarks/run.py`` persists to ``BENCH_scheduling.json`` so the
scheduler perf trajectory is tracked PR over PR."""
from __future__ import annotations

import itertools
import time

import numpy as np

from benchmarks.common import emit, timeit
from repro.core import scheduling

NOISE = 1.6e-14
PMAX = 0.01


def _instance(m, t, seed=0):
    rng = np.random.default_rng(seed)
    gains = np.abs(rng.normal(1e-6, 5e-7, (t, m))) + 1e-8
    w = rng.dirichlet(np.ones(m))
    return gains, w


def _loop_score(subs_vk, t, gains, w, power_fn):
    """The seed's candidate scorer: one group_weighted_rate call per subset."""
    return np.array([
        scheduling.group_weighted_rate(tuple(s), t, gains, w, power_fn, NOISE)[0]
        for s in subs_vk
    ])


def _candidate_scoring(fast: bool):
    """Batched engine vs per-subset loop on one round's candidate batch:
    M=300, K=3, pool of the 64 strongest -> C(64,3) = 41664 subsets."""
    pool = 32 if fast else 64
    gains, w = _instance(300, 1)
    power_fn = scheduling.make_power_fn("max", PMAX, NOISE)
    solo = w * np.log2(1.0 + (PMAX * gains[0] ** 2) / NOISE)
    keep = np.argsort(-solo)[:pool]
    subs = np.array(
        list(itertools.combinations(sorted(keep.tolist()), 3)), dtype=np.intp
    )
    us_loop = timeit(lambda: _loop_score(subs, 0, gains, w, power_fn), repeats=1)
    us_batch = timeit(
        lambda: scheduling.score_subsets(subs, 0, gains, w, power_fn, NOISE),
        repeats=3,
    )
    vals_loop = _loop_score(subs, 0, gains, w, power_fn)
    vals_batch = scheduling.score_subsets(subs, 0, gains, w, power_fn, NOISE)
    assert np.allclose(vals_loop, vals_batch, rtol=1e-12)
    emit(f"sched.score_loop_M300_pool{pool}", us_loop, f"{len(subs)} subsets")
    emit(
        f"sched.score_batched_M300_pool{pool}",
        us_batch,
        f"speedup {us_loop / us_batch:.1f}x",
    )


def _schedule_once(backend, gains, w, k, pool):
    if backend.startswith("jax"):
        # untimed warm-up: each (T, V, K) case shape compiles the jitted
        # step / fused loop once, and compile latency would otherwise
        # pollute the tracked per-schedule wall-clock
        scheduling.lazy_greedy_schedule(
            gains, w, k, noise_power=NOISE, candidate_pool=pool, backend=backend
        )
    t0 = time.perf_counter()
    s = scheduling.lazy_greedy_schedule(
        gains, w, k, noise_power=NOISE, candidate_pool=pool, backend=backend
    )
    return time.perf_counter() - t0, s


def backend_sweep(fast: bool):
    """M sweep x backend wall-clock for the lazy greedy (BENCH_scheduling.json).

    The numpy path re-enumerates C(pool, K) subsets per (step, round) in
    Python; "jax-stepwise" scores the whole (T, V, K) vertex tensor in one
    jitted call per greedy step but syncs the argmax scalars to the host
    every step; "jax" (fused) runs the entire selection loop inside a single
    ``lax.while_loop`` and syncs exactly once per schedule — the sweep
    measures the host-sync win directly.  M=3000 is device-only — the host
    path is impractical there, which is the point of the device-resident
    backend.
    """
    records = []
    cases = (
        [(100, 10, 3, 32, ("numpy", "jax-stepwise", "jax"))]
        if fast
        else [
            (300, 35, 3, 64, ("numpy", "jax-stepwise", "jax")),
            (1000, 50, 3, 64, ("numpy", "jax-stepwise", "jax")),
            (3000, 50, 3, 64, ("jax-stepwise", "jax")),
        ]
    )
    for m, t, k, pool, backends in cases:
        gains, w = _instance(m, t, seed=0)
        secs = {}
        for backend in backends:
            dt, s = _schedule_once(backend, gains, w, k, pool)
            s.validate(m, k)
            secs[backend] = dt
            records.append({
                "m": m, "t": t, "k": k, "pool": pool, "backend": backend,
                "seconds": round(dt, 4),
                "weighted_sum_rate": float(s.weighted_sum_rate),
            })
            emit(f"sched.lazy_{backend}_M{m}_T{t}_pool{pool}", dt * 1e6,
                 f"wsum {s.weighted_sum_rate:.3f}")
        if "numpy" in secs and "jax" in secs:
            emit(f"sched.backend_speedup_M{m}", 0.0,
                 f"{secs['numpy'] / secs['jax']:.1f}x fused jax over numpy")
        if "jax-stepwise" in secs and "jax" in secs:
            emit(f"sched.fused_vs_stepwise_M{m}", 0.0,
                 f"{secs['jax-stepwise'] / secs['jax']:.2f}x fused over "
                 f"stepwise (host-sync win)")
    # equality spot check on an instance small enough for every path
    g_eq, w_eq = _instance(48, 6, seed=1)
    a = scheduling.lazy_greedy_schedule(
        g_eq, w_eq, 3, noise_power=NOISE, candidate_pool=16
    )
    identical = True
    for backend in ("jax", "jax-stepwise"):
        b = scheduling.lazy_greedy_schedule(
            g_eq, w_eq, 3, noise_power=NOISE, candidate_pool=16, backend=backend
        )
        identical = identical and bool(
            a.rounds == b.rounds and a.weighted_sum_rate == b.weighted_sum_rate
        )
    # recorded, not asserted: a ULP tie-flip must not abort the perf-record
    # write — bit equality is pinned by tests/test_scheduling_edges.py
    emit("sched.backend_equality_M48", 0.0,
         "identical" if identical else "DIVERGED (see test suite)")
    return {"suite": "scheduling", "fast": fast,
            "backends_identical_M48": identical, "records": records}


def main(fast: bool = False):
    # literal vs lazy at small M (identical outputs; timing gap)
    gains, w = _instance(8, 3)
    us_lit = timeit(lambda: scheduling.literal_graph_schedule(
        gains, w, 2, noise_power=NOISE), repeats=3)
    us_lazy = timeit(lambda: scheduling.lazy_greedy_schedule(
        gains, w, 2, noise_power=NOISE), repeats=3)
    emit("sched.literal_M8", us_lit, "explicit C(M,K)*T graph")
    emit("sched.lazy_M8", us_lazy, f"speedup {us_lit / us_lazy:.1f}x")

    # optimality gap vs brute force
    gaps = []
    for seed in range(5):
        g2, w2 = _instance(6, 2, seed)
        greedy = scheduling.lazy_greedy_schedule(g2, w2, 2, noise_power=NOISE)
        best = scheduling.brute_force_schedule(g2, w2, 2, noise_power=NOISE)
        gaps.append(greedy.weighted_sum_rate / best.weighted_sum_rate)
    emit("sched.greedy_vs_optimal", 0.0, f"ratio {np.mean(gaps):.3f}")

    # batched rate engine vs the seed's per-subset loop (the PR's hot path)
    _candidate_scoring(fast)

    # paper scale: M=300, K=3, T=35 (infeasible for the literal graph:
    # C(300,3)*35 = 1.55e8 vertices)
    m, t = (100, 10) if fast else (300, 35)
    gains, w = _instance(m, t)
    t0 = time.perf_counter()
    s = scheduling.lazy_greedy_schedule(gains, w, 3, noise_power=NOISE)
    us = (time.perf_counter() - t0) * 1e6
    emit(f"sched.lazy_M{m}_T{t}", us,
         f"wsum {s.weighted_sum_rate:.3f} literal_would_need "
         f"{35 * 4455100 if not fast else 10 * 161700} vertices")
    s.validate(m, 3)

    # larger candidate pools, reachable now that scoring is batched (the
    # seed's Python loop capped practical pools at ~16)
    for pool in (16, 48):
        t0 = time.perf_counter()
        sp = scheduling.lazy_greedy_schedule(
            gains, w, 3, noise_power=NOISE, candidate_pool=pool
        )
        us = (time.perf_counter() - t0) * 1e6
        emit(f"sched.lazy_M{m}_pool{pool}", us,
             f"wsum {sp.weighted_sum_rate:.3f}")

    # numpy vs jax device-resident greedy; records land in
    # BENCH_scheduling.json via benchmarks/run.py
    return backend_sweep(fast)


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
