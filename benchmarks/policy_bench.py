"""Online-policy horizon benchmark: traced scan vs the per-round loop.

Before PR 10 an online policy (update-aware, age-fair, matching-pursuit)
forced ``horizon = "per-round"``: every round paid a host round-trip —
selection on the host, power/rate finalization, budget packing, then one
device dispatch.  The traced selection protocol folds all of it into the
scanned horizon (``fl_engine._online_horizon_core``), so the whole
horizon is ONE device program with ONE host sync.

This suite measures the end-to-end horizon wall time (warm-compiled, best
of 2 passes) of ``fl.run_federated_learning`` for the same config under
``horizon in {scan, per-round}`` with the update-aware policy — the
norm-fed policy whose FL-state feedback previously *required* the host
loop.  Like the fl_cells suite, ``speedup`` is vs the repo's default
per-round driver (legacy engine — one dispatch per device per round plus
the per-round host selection/finalization/norm syncs), and
``speedup_vs_batched`` isolates what the traced scan adds on top of the
PR 5 batched round engine.  ``benchmarks/run.py`` persists the records to
``BENCH_policy.json`` (``BENCH_policy_fast.json`` under --fast/--smoke).

Settings: max power (the traced allocator), adaptive compression, NOMA
uplink — identical physics on both paths; tests/test_policy_scan.py pins
that scan and per-round produce identical schedules/bits/rates/times.
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


def _horizon_seconds(ds, shards, cell, cfg, *, passes: int = 2) -> float:
    """Whole-horizon wall time, warm-compiled, best of ``passes``."""
    fl.run_federated_learning(ds, shards, cell, cfg, eval_every=10**9)
    best = np.inf
    for _ in range(passes):
        t0 = time.perf_counter()
        fl.run_federated_learning(ds, shards, cell, cfg, eval_every=10**9)
        best = min(best, time.perf_counter() - t0)
    return best


def main(fast: bool = False) -> dict:
    if fast:
        cases = [(60, 3)]
        rounds, samples = 3, 1500
    else:
        cases = [(300, 8), (1000, 8)]
        rounds, samples = 6, 12_000
    scheduler = "update-aware"
    records = []
    for m, k in cases:
        gc.collect()
        ds = make_mnist_like(num_samples=samples, seed=0)
        cell = channel.CellConfig(num_devices=m)
        shards = dirichlet_partition(ds.y_train, m, seed=0)
        cfg = FLConfig(
            num_devices=m, group_size=k, num_rounds=rounds,
            scheduler=scheduler, power_mode="max",
            compression="adaptive", fl_engine="batched",
            horizon="scan", seed=0,
        )
        scan_s = _horizon_seconds(ds, shards, cell, cfg)
        batched_s = _horizon_seconds(
            ds, shards, cell, dataclasses.replace(cfg, horizon="per-round")
        )
        legacy_s = _horizon_seconds(
            ds, shards, cell,
            dataclasses.replace(cfg, horizon="per-round", fl_engine="legacy"),
        )
        speedup = legacy_s / scan_s
        records.append({
            "scheduler": scheduler, "m": m, "k": k, "rounds": rounds,
            "scan_horizon_s": scan_s,
            "per_round_legacy_horizon_s": legacy_s,
            "per_round_batched_horizon_s": batched_s,
            "speedup": round(speedup, 2),
            "speedup_vs_batched": round(batched_s / scan_s, 2),
        })
        emit(f"policy.scan_M{m}_K{k}", scan_s * 1e6)
        emit(f"policy.per_round_M{m}_K{k}", legacy_s * 1e6,
             f"speedup {speedup:.1f}x")
    return {
        "suite": "online_policy_horizon",
        "settings": {
            "scheduler": scheduler, "power_mode": "max",
            "compression": "adaptive", "uplink": "noma",
            "rounds": rounds, "num_samples": samples,
        },
        "records": records,
    }


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
