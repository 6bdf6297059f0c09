"""Perf hillclimb harness (EXPERIMENTS.md §Perf).

For a chosen (arch x shape) pair, compile a set of lever variants and report
the roofline-term deltas. Two measurements per variant:

  * component-extrapolated roofline (reduced-depth UNROLLED compiles) — the
    compute/memory/collective terms; levers act per-layer so reduced-depth
    deltas transfer to full depth;
  * full-depth SCANNED compile — per-device memory_analysis (the "fits"
    check).

    PYTHONPATH=src python -m benchmarks.hillclimb --arch zamba2-7b \
        --shape train_4k --variants baseline,accum8,accum8_noremat

NOTE: must run in a fresh process (sets the 512-device XLA flag).
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

import argparse
import json

VARIANTS = {
    # name -> kwargs for run_one / roofline_extrapolated
    "baseline": {},
    "accum4": {"grad_accum": 4},
    "accum8": {"grad_accum": 8},
    "accum16": {"grad_accum": 16},
    "accum8_noremat": {"grad_accum": 8, "remat": False},
    "noremat": {"remat": False},
    "nofl": {"fl_bits": 32},
    "kv512": {"kv_chunk_train": 512},
    "kv2048": {"kv_chunk_train": 2048},
    "kv4096": {"kv_chunk_train": 4096},
    "kvdec1024": {"kv_chunk_decode": 1024},
    "kvdec16384": {"kv_chunk_decode": 16384},
    "ssd64": {"cfg_override": {"ssm_chunk": 64}},
    "ssd256": {"cfg_override": {"ssm_chunk": 256}},
    "ssd64_accum8": {"cfg_override": {"ssm_chunk": 64}, "grad_accum": 8},
    "accum16_v": {"grad_accum": 16},
    "ssmbf16": {"cfg_override": {"ssm_bf16": True}},
    "ssmbf16_accum8": {"cfg_override": {"ssm_bf16": True}, "grad_accum": 8},
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--variants", default="baseline,accum8")
    ap.add_argument("--skip-mem", action="store_true",
                    help="skip the full-depth scanned memory compile")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    from repro.launch.dryrun import roofline_extrapolated, run_one

    rows = []
    for name in args.variants.split(","):
        kw = dict(VARIANTS[name])
        fl_bits = kw.pop("fl_bits", 8)
        roof = roofline_extrapolated(args.arch, args.shape, fl_bits=fl_bits,
                                     verbose=False, **kw)
        mem = None
        if not args.skip_mem:
            mem = run_one(args.arch, args.shape, unroll=False, verbose=False,
                          fl_bits=fl_bits, **kw)
        row = {"variant": name, "kw": {**kw, "fl_bits": fl_bits}}
        if roof is not None and roof.status == "OK":
            s = roof.roofline
            row.update(
                t_compute_ms=s["t_compute_s"] * 1e3,
                t_memory_ms=s["t_memory_s"] * 1e3,
                t_collective_ms=s["t_collective_s"] * 1e3,
                bottleneck=s["bottleneck"],
                useful=s["useful_flops_ratio"],
            )
        if mem is not None and mem.status == "OK":
            row["mem_per_dev_gib"] = mem.bytes_per_device / 2**30
            row["compile_s"] = mem.compile_s
        if mem is not None and mem.status == "FAIL":
            row["mem_error"] = mem.error[:120]
        rows.append(row)
        print(json.dumps(row), flush=True)

    if args.out:
        with open(args.out, "a") as f:
            for r in rows:
                f.write(json.dumps({"arch": args.arch, "shape": args.shape,
                                    **r}) + "\n")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
