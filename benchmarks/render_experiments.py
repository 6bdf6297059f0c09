"""Render the EXPERIMENTS.md §Dry-run and §Roofline tables from the JSONL
artifacts in results/. Usage:

    PYTHONPATH=src python -m benchmarks.render_experiments > /tmp/tables.md
"""
from __future__ import annotations

import json
import os

RESULTS = os.path.join(os.path.dirname(__file__), "..", "results")


def load(name):
    path = os.path.join(RESULTS, name)
    if not os.path.exists(path):
        return []
    with open(path) as f:
        return [json.loads(l) for l in f]


def fmt_bytes(b):
    return f"{b / 2**30:.2f}"


def dryrun_table(rows, title):
    out = [f"### {title}", "",
           "| arch | shape | status | compile s | mem/dev GiB | notes |",
           "|---|---|---|---|---|---|"]
    for r in rows:
        note = r.get("error", "")[:60] if r["status"] != "OK" else ""
        mem = fmt_bytes(r["bytes_per_device"]) if r["status"] == "OK" else "-"
        cs = f"{r['compile_s']:.0f}" if r["status"] == "OK" else "-"
        out.append(f"| {r['arch']} | {r['shape']} | {r['status']} | {cs} | "
                   f"{mem} | {note} |")
    ok = sum(r["status"] == "OK" for r in rows)
    fail = sum(r["status"] == "FAIL" for r in rows)
    skip = sum(r["status"] == "SKIP" for r in rows)
    out += ["", f"**{ok} OK / {fail} FAIL / {skip} SKIP**", ""]
    return "\n".join(out)


def roofline_table(rows):
    out = ["| arch | shape | t_comp ms | t_mem ms | t_coll ms | bottleneck | "
           "useful | AG GiB | AR GiB | RS GiB | A2A GiB |",
           "|---|---|---|---|---|---|---|---|---|---|---|"]
    for r in rows:
        if r["status"] != "OK":
            out.append(f"| {r['arch']} | {r['shape']} | {r['status']}: "
                       f"{r.get('error','')[:40]} | | | | | | | | |")
            continue
        s = r["roofline"]
        cb = s["collective_breakdown"]
        out.append(
            f"| {r['arch']} | {r['shape']} | {s['t_compute_s']*1e3:.2f} | "
            f"{s['t_memory_s']*1e3:.2f} | {s['t_collective_s']*1e3:.2f} | "
            f"**{s['bottleneck']}** | {s['useful_flops_ratio']:.2f} | "
            f"{cb.get('all-gather',0)/2**30:.2f} | "
            f"{cb.get('all-reduce',0)/2**30:.2f} | "
            f"{cb.get('reduce-scatter',0)/2**30:.2f} | "
            f"{cb.get('all-to-all',0)/2**30:.2f} |")
    return "\n".join(out)


def main(fast: bool = False):
    sp = load("dryrun_single_pod.jsonl")
    mp = load("dryrun_multi_pod.jsonl")
    rf = load("roofline.jsonl")
    if sp:
        print(dryrun_table(sp, "Single-pod mesh (data=16, model=16) = 256 chips"))
    if mp:
        print(dryrun_table(mp, "Multi-pod mesh (pod=2, data=16, model=16) = 512 chips"))
    if rf:
        print("### Roofline (single-pod, depth-extrapolated, per-chip seconds)\n")
        print(roofline_table(rf))


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
