"""Every file the benchmark is made of loads, and every name in it keeps
to the characters the benchmark's contract allows."""
import json
import pathlib
import re

import pytest

from chipbench import harness

ROOT = harness.ROOT
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source", "workloads"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves",
                  "workloads"},
}


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for path in BENCH["paths"]:
        assert (ROOT / path).is_dir()
    assert (ROOT / BENCH["command"][1]).is_file()


@pytest.mark.parametrize("section", sorted(KEYS))
def test_entries_keep_to_their_keys_and_names(section):
    names = [e["name"] for e in BENCH[section]]
    assert len(names) == len(set(names))
    for e in BENCH[section]:
        assert set(e) <= KEYS[section], e
        assert NAME.fullmatch(e["name"]), e["name"]
        if "unit" in e:
            assert UNIT.fullmatch(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")
        for text in (e.get("why", "x"), e.get("layer", "x")):
            assert 1 <= len(text) <= 200 and "\n" not in text


def test_configs_load():
    for c in BENCH["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["name"] == c["name"]
        assert conf["source"] == c["source"]
        ref = harness.load_module(harness.BENCH_DIR / "references"
                                  / f"{conf['reference']}.py")
        assert ref.param_count(conf) == conf["model"]["params"]
        train, evaluated = ref.sample_flops(conf)
        assert train > evaluated > 0


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_cells_load(name):
    cell = harness.load_cell(name)
    assert NAME.fullmatch(cell.traffic["entry"])
    assert (harness.BENCH_DIR / "entries"
            / f"{cell.traffic['entry']}.py").is_file()
    assert cell.spec["limits"]
    assert {"rounds_per_s", "setup_s"} <= {m["name"] for m in cell.end_to_end}
    assert cell.per_layer


@pytest.mark.parametrize("name", [m["name"] for m in BENCH["per_layer"]])
def test_metric_readers_load(name):
    mod = harness.load_module(harness.BENCH_DIR / "metrics" / f"{name}.py")
    assert callable(mod.read)


def test_every_file_name_is_made_of_name_characters():
    for path in harness.BENCH_DIR.rglob("*"):
        if "__pycache__" in path.parts:
            continue
        rel = path.relative_to(ROOT).as_posix()
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]+", rel), rel


def test_peaks_table_refuses_an_unknown_kind():
    assert harness._peak("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        harness._peak("cpu")
