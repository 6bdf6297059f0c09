"""Roofline table assembly: reads the dry-run JSONL artifacts produced by
repro.launch.dryrun and prints the per-(arch x shape) table used in
EXPERIMENTS.md §Roofline. Does NOT compile anything itself (runs in seconds;
regenerate the JSONL with the dryrun CLI)."""
from __future__ import annotations

import json
import os

from benchmarks.common import emit

RESULTS = os.path.join(os.path.dirname(__file__), "..", "results")


def load(name):
    path = os.path.join(RESULTS, name)
    if not os.path.exists(path):
        return []
    out = []
    with open(path) as f:
        for line in f:
            out.append(json.loads(line))
    return out


def main(fast: bool = False):
    # v2 = post-perf-iteration sweep (activation-sharding constraints);
    # the v1 file is the frozen baseline table.
    roof = load("roofline_v2.jsonl") or load("roofline.jsonl")
    if not roof:
        emit("roofline.missing", 0.0,
             "run: python -m repro.launch.dryrun --all --roofline --out results/roofline.jsonl")
        return
    n_ok = 0
    for r in roof:
        if r["status"] != "OK":
            continue
        n_ok += 1
        s = r["roofline"]
        emit(
            f"roofline.{r['arch']}.{r['shape']}",
            s["t_compute_s"] * 1e6,
            f"bottleneck={s['bottleneck']} "
            f"t_mem_us={s['t_memory_s'] * 1e6:.0f} "
            f"t_coll_us={s['t_collective_s'] * 1e6:.0f} "
            f"useful={s['useful_flops_ratio']:.2f}",
        )
    emit("roofline.pairs_ok", 0.0, str(n_ok))
    for name in ("dryrun_single_pod.jsonl", "dryrun_multi_pod.jsonl"):
        rows = load(name)
        ok = sum(r["status"] == "OK" for r in rows)
        fail = sum(r["status"] == "FAIL" for r in rows)
        skip = sum(r["status"] == "SKIP" for r in rows)
        emit(f"dryrun.{name.split('.')[0]}", 0.0,
             f"ok={ok} fail={fail} skip={skip}")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
