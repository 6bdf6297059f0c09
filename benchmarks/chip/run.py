"""Run one cell of the on-chip benchmark once (see chipbench/harness.py).

    python benchmarks/chip/run.py --workload paper-noma.mapel-gwmin \
        --seed 7 --seconds 20 --trace 0
"""
import os
import sys
import time

T0 = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], t0=T0))
