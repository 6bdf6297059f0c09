"""``run.py`` refuses to measure where it cannot: with no TPU, and in a
checkout that holds only the benchmark."""
import json
import os
import shutil
import subprocess
import sys

from chipbench import harness

ARGS = ["--workload", "paper-noma.mapel-gwmin", "--seed", "3",
        "--seconds", "1", "--trace", "0"]


def _run(cwd, env_extra):
    env = {**os.environ, **env_extra}
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", *ARGS], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def _no_result(out):
    for line in out.splitlines():
        try:
            assert not isinstance(json.loads(line), dict)
        except ValueError:
            pass


def test_no_tpu_exits_nonzero_without_a_result(tmp_path):
    proc = _run(harness.ROOT, {"JAX_PLATFORMS": "cpu",
                               "JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    _no_result(proc.stdout)


def test_benchmark_alone_exits_nonzero_without_a_result(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(harness.BENCH_DIR, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    _no_result(proc.stdout)
