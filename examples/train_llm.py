"""Train an assigned architecture (reduced config) with the paper's
gradient-compression uplink in the loop.

    PYTHONPATH=src python examples/train_llm.py [--arch mixtral-8x22b] [--steps 30]

Demonstrates the LLM-scale integration (DESIGN.md §2): each optimizer step
quantizes the gradient pytree to the bit-width the NOMA rate model allows
that round. Full-scale configs are exercised via the dry-run
(repro.launch.dryrun), not by training on CPU.
"""
import argparse
import sys

from repro.launch.train import main as train_main

if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mixtral-8x22b")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--fl-bits", type=int, default=8)
    args = ap.parse_args()
    train_main(["--arch", args.arch, "--smoke", "--steps", str(args.steps),
                "--batch", "8", "--seq", "128", "--fl-bits", str(args.fl_bits)])
