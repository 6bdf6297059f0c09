"""The four-chip cell on four virtual CPU devices, with the look for a
chip skipped: one subprocess (the device count is fixed when JAX starts)
runs the tiny ``paper-noma.cell-sweep-4chip`` once sound and once under
each planted fault, and the tests read its result lines."""
import json
import os
import subprocess
import sys

import pytest

from chipbench import harness, testing

CELL = "paper-noma.cell-sweep-4chip"
DRIVER = f"""
import json, sys
sys.path.insert(0, {str(harness.BENCH_DIR)!r})
sys.path.insert(0, {str(harness.ROOT / 'src')!r})
from chipbench import testing
for fault in [None] + sorted(testing.FAULTS):
    result = testing.run_tiny({CELL!r}, seed=2**31 + 11, fault=fault)
    print(json.dumps({{"fault": fault, "result": result}}), flush=True)
"""


@pytest.fixture(scope="module")
def results(tmp_path_factory):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path_factory.mktemp("jc"))}
    proc = subprocess.run([sys.executable, "-c", DRIVER], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    lines = [json.loads(x) for x in proc.stdout.splitlines()
             if x.startswith("{")]
    return {x["fault"]: x["result"] for x in lines}


def test_sound_run_is_correct(results):
    result = results[None]
    assert result["correct"], result["checks"]
    assert result["failed"] == 0
    assert result["device"]["count"] == 4


@pytest.mark.parametrize("fault", sorted(testing.FAULTS))
def test_fault_is_not_correct(results, fault):
    result = results[fault]
    assert not result["correct"], result["checks"]
    assert result["failed"] == result["attempted"]
