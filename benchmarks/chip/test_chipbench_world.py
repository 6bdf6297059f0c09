"""``build_world`` makes a configuration's data with the generator its
``data.kind`` names (``images`` where it names none)."""
import json

import numpy as np
import pytest

from chipbench import harness, world

BENCH = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
CONFIGS = [c["file"] for c in BENCH["configs"]]


@pytest.mark.parametrize("path", CONFIGS)
def test_build_world_is_its_kinds_generator(path):
    config = json.loads((harness.ROOT / path).read_text())
    config["fl"]["num_devices"] = 24
    config["data"]["num_samples"] = 600
    kind = config["data"].get("kind", "images")
    direct = harness.load_module(
        harness.BENCH_DIR / "worlds" / f"{kind}.py").build(config, 2**31 + 3)
    got = world.build_world(config, 2**31 + 3)
    for name in ("x_train", "y_train", "x_test", "y_test"):
        np.testing.assert_array_equal(getattr(got.dataset, name),
                                      getattr(direct.dataset, name))
    np.testing.assert_array_equal(got.sizes, direct.sizes)
    assert len(got.shards) == 24
    for a, b in zip(got.shards, direct.shards):
        np.testing.assert_array_equal(a, b)
    assert sorted(np.concatenate(got.shards)) == list(
        range(len(got.dataset.y_train)))
    assert got.config is config


def test_unknown_kind_raises():
    config = json.loads((harness.ROOT / CONFIGS[0]).read_text())
    config["data"]["kind"] = "no-such-kind"
    with pytest.raises(KeyError, match="no-such-kind.py"):
        world.build_world(config, 1)
