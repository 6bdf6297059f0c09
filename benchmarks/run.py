"""Benchmark harness: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--fast] [--smoke] [--only NAME]
                                            [--check-regression]

``--smoke`` is the CI mode: implies ``--fast`` and skips the FL-training
suites (fig5/fig6) plus the roofline sweep, so the job finishes in minutes
while still exercising the power, scheduling, kernel, and compression paths.

``--check-regression`` gates the persisted suites: after the fresh records
are written, each timing metric is compared against the committed baseline
JSON (same filename, snapshotted before the run overwrites it) and the run
fails if the *median* fresh/baseline ratio over all matched records exceeds
1.20 — a single noisy record doesn't trip it, a broad slowdown does.

Prints ``name,us_per_call,derived`` CSV rows (benchmarks/common.emit).
The scheduling, fl_engine and fl_cells suites additionally return sweep
records that are persisted at the repo root (``BENCH_scheduling.json``: M
sweep x numpy/jax scheduler backend; ``BENCH_fl.json``: K x M round-loop
sweep, legacy vs batched FL engine; ``BENCH_cells.json``: cells x seeds x M
sweep, scanned grid vs sequential per-round dispatch;
``BENCH_policy.json``: online-policy horizons, traced scan vs per-round
host loop; ``BENCH_payload.json``: transformer-class payload-size sweep,
chunked Pallas aggregation vs XLA einsum) so the perf trajectories are
tracked from PR to PR.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import statistics
import sys
import traceback

# "module" or "module:function" (default function: main)
SUITES = [
    ("power", "benchmarks.power_bench"),           # §III-C / ref [8]
    ("scheduling", "benchmarks.scheduling_bench"), # §III-A/B Algorithm 2
    ("kernels", "benchmarks.kernel_bench"),        # §II-B codec hot-spot
    ("compression", "benchmarks.compression_stats"),  # §II-B adaptive bits
    ("fl_engine", "benchmarks.fl_bench"),          # legacy vs batched round loop
    ("fl_cells", "benchmarks.fl_bench:cells_main"),  # scanned cells x seeds sweep
    ("policy", "benchmarks.policy_bench"),         # online-policy traced scan
    ("payload", "benchmarks.payload_bench"),       # LLM-scale aggregation
    ("ota", "benchmarks.ota_bench"),               # analog vs digital uplink
    ("fig5", "benchmarks.fig5_noma_vs_tdma"),      # Fig. 5
    ("fig6", "benchmarks.fig6_schemes"),           # Fig. 6
    ("roofline", "benchmarks.roofline_bench"),     # EXPERIMENTS §Roofline
]

# FL-training suites (minutes even at --fast) and the roofline sweep are out
# of scope for the CI smoke job.  fl_engine/fl_cells stay in: their --fast
# cases are one tiny cell each and they are the smoke signals for the
# batched round engine and the scanned sweep driver regressing.
SMOKE_SKIP = {"fig5", "fig6", "roofline"}

# Suites whose main() returns a dict of records persisted at the repo root
# (suffixed _fast under --fast/--smoke so the tracked full-sweep record is
# never clobbered by a small run).
PERSIST = {
    "scheduling": "BENCH_scheduling",
    "fl_engine": "BENCH_fl",
    "fl_cells": "BENCH_cells",
    "policy": "BENCH_policy",
    "payload": "BENCH_payload",
    "ota": "BENCH_ota",
}

# --check-regression: per-suite wall-time metrics (everything else in a
# record is part of its identity key).  Derived columns like "speedup" are
# deliberately absent — they are ratios of these, and gating them twice
# would double-count noise.
REGRESSION_METRICS = {
    "scheduling": ("seconds",),
    "fl_engine": ("legacy_s_per_round", "batched_s_per_round"),
    "fl_cells": ("scan_sweep_s", "per_round_legacy_sweep_s",
                 "per_round_batched_sweep_s"),
    "policy": ("scan_horizon_s", "per_round_legacy_horizon_s",
               "per_round_batched_horizon_s"),
    "payload": ("einsum_s", "pallas_chunked_s"),
    "ota": ("horizon_s",),
}
REGRESSION_THRESHOLD = 1.20


def _record_key(record: dict, metrics: tuple):
    return tuple(sorted(
        (k, v) for k, v in record.items()
        if k not in metrics and not k.startswith("speedup")
    ))


def check_regression(name: str, fresh: dict, baseline: dict) -> list:
    """Median fresh/baseline ratio per metric; returns failure strings."""
    metrics = REGRESSION_METRICS[name]
    base_by_key = {
        _record_key(r, metrics): r for r in baseline.get("records", [])
    }
    failures = []
    for metric in metrics:
        ratios = []
        for rec in fresh.get("records", []):
            base = base_by_key.get(_record_key(rec, metrics))
            if base is None or metric not in base or metric not in rec:
                continue
            if base[metric] > 0:
                ratios.append(rec[metric] / base[metric])
        if not ratios:
            print(f"# regression-check {name}.{metric}: no matching "
                  f"baseline records, skipped", flush=True)
            continue
        med = statistics.median(ratios)
        status = "OK" if med <= REGRESSION_THRESHOLD else "REGRESSED"
        print(f"# regression-check {name}.{metric}: median ratio "
              f"{med:.3f} over {len(ratios)} records ({status})", flush=True)
        if med > REGRESSION_THRESHOLD:
            failures.append(f"{name}.{metric} median ratio {med:.3f} > "
                            f"{REGRESSION_THRESHOLD}")
    return failures


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="CI subset: --fast minus the FL-training suites")
    ap.add_argument("--only", default=None)
    ap.add_argument("--check-regression", action="store_true",
                    help="fail if a persisted suite's fresh timings are "
                         ">20%% (median) over the committed baseline JSON")
    args = ap.parse_args()
    fast = args.fast or args.smoke
    suffix = "_fast" if fast else ""
    root = pathlib.Path(__file__).resolve().parent.parent

    # Snapshot the committed baselines BEFORE the suites overwrite them.
    baselines = {}
    if args.check_regression:
        for name, stem in PERSIST.items():
            path = root / f"{stem}{suffix}.json"
            if path.exists():
                baselines[name] = json.loads(path.read_text())

    import importlib

    failures = []
    regressions = []
    for name, target in SUITES:
        if args.only and args.only != name:
            continue
        if args.smoke and name in SMOKE_SKIP and args.only != name:
            continue
        module, _, func = target.partition(":")
        print(f"# === {name} ({target}) ===", flush=True)
        try:
            entry = getattr(importlib.import_module(module), func or "main")
            result = entry(fast=fast)
            if name in PERSIST and isinstance(result, dict):
                out = root / f"{PERSIST[name]}{suffix}.json"
                out.write_text(json.dumps(result, indent=2) + "\n")
                print(f"# wrote {out}", flush=True)
                if args.check_regression:
                    if name in baselines:
                        regressions += check_regression(
                            name, result, baselines[name])
                    else:
                        print(f"# regression-check {name}: no committed "
                              f"baseline, skipped", flush=True)
        except Exception:
            failures.append(name)
            traceback.print_exc()
    if failures:
        print(f"# FAILED suites: {failures}")
        sys.exit(1)
    if regressions:
        print(f"# PERF REGRESSIONS: {regressions}")
        sys.exit(1)
    print("# all suites ok")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
