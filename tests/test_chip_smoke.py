"""chip_smoke.py on the CPU: it refuses to run, and its checks hold here.

The script's phases are only meaningful on a TPU, so on the CPU ``main``
must stop at the device check with a non-zero code and print no result.
The phases themselves are plain functions of a world and a config; run at a
small size here they pin that the script's comparisons keep passing as the
drivers change.  Also pinned: the compile-cache helper every entry point
calls.
"""
import importlib.util
import pathlib
import sys

import jax
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py"
    )
    mod = importlib.util.module_from_spec(spec)
    # its dataclass resolves annotations through sys.modules
    sys.modules.setdefault("chip_smoke", mod)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_main_refuses_without_a_tpu(smoke, capsys, argv):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main(argv) != 0
    out = capsys.readouterr()
    assert out.out == ""
    assert "no TPU found" in out.err


def test_phases_pass_on_a_small_world(smoke):
    world = smoke.paper_world(12, 800, 0)
    cfg = smoke.paper_config(12, 4, 0)
    scan = smoke.phase_paper_horizon(world, cfg)
    smoke.phase_online_scan(world, cfg)
    smoke.phase_pallas(world, cfg, scan)


def test_check_failure_raises(smoke):
    with pytest.raises(smoke.CheckFailed, match="differ"):
        smoke.check(False, "schedules differ")


def test_compile_cache_fixed_path_inside_checkout(monkeypatch):
    from repro.utils import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_leaves_env_setting_to_jax(monkeypatch, tmp_path):
    from repro.utils import compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
