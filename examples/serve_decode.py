"""Serve a small model with batched requests: prefill + greedy decode.

    PYTHONPATH=src python examples/serve_decode.py [--arch zamba2-7b]

Uses the reduced same-family config on CPU; the production decode shapes
(decode_32k / long_500k) are exercised via the dry-run.
"""
import argparse

from repro.launch.serve import main as serve_main

if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="zamba2-7b")
    ap.add_argument("--batch", type=int, default=4)
    args = ap.parse_args()
    serve_main(["--arch", args.arch, "--smoke", "--batch", str(args.batch),
                "--prompt-len", "32", "--gen", "16"])
