"""FL round-engine benchmark: legacy per-device loop vs batched engine.

Measures the steady-state **round-loop** time of ``fl.run_federated_learning``
(median per-round wall time from the progress callbacks, so setup —
channel sampling, scheduling, ClientBank build, jit compilation — is
excluded) for ``fl_engine in {legacy, batched}`` over the K x M sweep the
batched engine exists for.  ``benchmarks/run.py`` persists the records to
``BENCH_fl.json`` (``BENCH_fl_fast.json`` under --fast/--smoke) so the
round-loop speedup is tracked from PR to PR.

Settings: round-robin scheduling (cheap, deterministic, K devices every
round), max power, adaptive compression, NOMA uplink — the round body is
the only thing that differs between the two engines.

:func:`cells_main` (suite ``fl_cells`` -> ``BENCH_cells.json``) benchmarks
the scanned multi-cell driver instead: a whole cells x seeds instance grid
as ONE ``fl.run_cell_sweep`` device program vs the same instances
dispatched sequentially through the per-round batched driver.
"""
from __future__ import annotations

import dataclasses
import gc
import time

import numpy as np

from benchmarks.common import emit
from repro.config import FLConfig
from repro.core import channel, fl
from repro.data import dirichlet_partition, make_mnist_like


def _per_round_seconds(ds, shards, cell, cfg, *, passes: int = 2):
    """Median steady-state round time: warm-compile run, then measure the
    deltas between progress callbacks (covers rounds 1..R-1; setup and the
    round-0 tail of compilation land before the first delta).  Best of
    ``passes`` timed runs, so a background hiccup in one pass does not
    poison the record."""
    fl.run_federated_learning(ds, shards, cell, cfg, eval_every=10**9)
    best = np.inf
    for _ in range(passes):
        ts = []
        fl.run_federated_learning(
            ds, shards, cell, cfg, eval_every=10**9,
            progress=lambda log: ts.append(time.perf_counter()),
        )
        best = min(best, float(np.median(np.diff(ts))))
    return best


def _cells_scanned_s(ds, shards, cell, cfg, cells, seeds, *, passes=2):
    """Wall time of the whole (cells x seeds) sweep as ONE scanned-horizon
    dispatch (fl.run_cell_sweep), warm-compiled; best of ``passes``."""
    fl.run_cell_sweep(ds, shards, cell, cfg, num_cells=cells,
                      seeds_per_cell=seeds, eval_every=10**9)
    best = np.inf
    for _ in range(passes):
        t0 = time.perf_counter()
        fl.run_cell_sweep(ds, shards, cell, cfg, num_cells=cells,
                          seeds_per_cell=seeds, eval_every=10**9)
        best = min(best, time.perf_counter() - t0)
    return best


def _cells_per_round_s(ds, shards, cell, cfg, cells, seeds, *, engine,
                       passes=2):
    """The same cells x seeds instance grid run the pre-scan way: one
    sequential per-round driver call per instance, each paying its own
    setup and T round dispatches.  ``engine = "legacy"`` is the repo's
    default per-round driver (one dispatch per *device* per round);
    ``"batched"`` is the PR 5 engine (one dispatch per round)."""
    base = dataclasses.replace(cfg, horizon="per-round", fl_engine=engine)

    def sweep():
        for c in range(cells):
            for s in range(seeds):
                fl.run_federated_learning(
                    ds, shards, cell,
                    dataclasses.replace(base, seed=cfg.seed + c * seeds + s),
                    eval_every=10**9,
                )

    sweep()   # warm the per-(K, nb) round-step jit cache
    best = np.inf
    for _ in range(passes):
        t0 = time.perf_counter()
        sweep()
        best = min(best, time.perf_counter() - t0)
    return best


def cells_main(fast: bool = False) -> dict:
    """Multi-cell sweep benchmark: scanned cells x seeds grid
    (fl.run_cell_sweep — shared bank, one compiled horizon program) vs
    sequential per-round dispatch of the identical instances, against both
    per-round engines.  ``speedup`` is vs the repo's default per-round
    driver (legacy engine); ``speedup_vs_batched`` isolates what the scan
    adds on top of PR 5's one-dispatch-per-round engine.  Persisted to
    BENCH_cells.json by benchmarks/run.py."""
    if fast:
        cases = [(2, 2, 60, 3)]
        rounds, samples = 3, 1500
    else:
        cases = [(2, 2, 300, 8), (4, 2, 1000, 8), (2, 2, 1000, 8)]
        rounds, samples = 6, 12_000
    records = []
    for cells, seeds, m, k in cases:
        gc.collect()
        ds = make_mnist_like(num_samples=samples, seed=0)
        cell = channel.CellConfig(num_devices=m)
        shards = dirichlet_partition(ds.y_train, m, seed=0)
        cfg = FLConfig(
            num_devices=m, group_size=k, num_rounds=rounds,
            scheduler="round-robin", power_mode="max",
            compression="adaptive", fl_engine="batched", horizon="scan",
            seed=0,
        )
        scan_s = _cells_scanned_s(ds, shards, cell, cfg, cells, seeds)
        batched_s = _cells_per_round_s(ds, shards, cell, cfg, cells, seeds,
                                       engine="batched")
        legacy_s = _cells_per_round_s(ds, shards, cell, cfg, cells, seeds,
                                      engine="legacy")
        speedup = legacy_s / scan_s
        records.append({
            "cells": cells, "seeds": seeds, "m": m, "k": k, "rounds": rounds,
            "scan_sweep_s": scan_s,
            "per_round_legacy_sweep_s": legacy_s,
            "per_round_batched_sweep_s": batched_s,
            "speedup": round(speedup, 2),
            "speedup_vs_batched": round(batched_s / scan_s, 2),
        })
        emit(f"fl.cells_scan_C{cells}_S{seeds}_M{m}_K{k}", scan_s * 1e6)
        emit(f"fl.cells_per_round_C{cells}_S{seeds}_M{m}_K{k}",
             legacy_s * 1e6, f"speedup {speedup:.1f}x")
    return {
        "suite": "fl_cell_sweep",
        "settings": {
            "scheduler": "round-robin", "power_mode": "max",
            "compression": "adaptive", "uplink": "noma",
            "rounds": rounds, "num_samples": samples,
        },
        "records": records,
    }


def main(fast: bool = False) -> dict:
    if fast:
        cases = [(60, 3)]
        rounds, samples = 4, 1500
    else:
        cases = [(m, k) for m in (300, 1000) for k in (3, 8, 16)]
        rounds, samples = 6, 12_000
    records = []
    for m, k in cases:
        gc.collect()   # drop the previous case's dataset + ClientBank now
        ds = make_mnist_like(num_samples=samples, seed=0)
        cell = channel.CellConfig(num_devices=m)
        shards = dirichlet_partition(ds.y_train, m, seed=0)
        cfg = FLConfig(
            num_devices=m, group_size=k, num_rounds=rounds,
            scheduler="round-robin", power_mode="max",
            compression="adaptive", seed=0,
        )
        legacy_s = _per_round_seconds(ds, shards, cell, cfg)
        batched_s = _per_round_seconds(
            ds, shards, cell, dataclasses.replace(cfg, fl_engine="batched")
        )
        speedup = legacy_s / batched_s
        records.append({
            "m": m, "k": k, "rounds": rounds,
            "legacy_s_per_round": legacy_s,
            "batched_s_per_round": batched_s,
            "speedup": round(speedup, 2),
        })
        emit(f"fl.round_legacy_M{m}_K{k}", legacy_s * 1e6)
        emit(f"fl.round_batched_M{m}_K{k}", batched_s * 1e6,
             f"speedup {speedup:.1f}x")
    return {
        "suite": "fl_engine_round_loop",
        "settings": {
            "scheduler": "round-robin", "power_mode": "max",
            "compression": "adaptive", "uplink": "noma",
            "rounds": rounds, "num_samples": samples,
        },
        "records": records,
    }


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
