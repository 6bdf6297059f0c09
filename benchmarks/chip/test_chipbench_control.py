"""The control: the plain reference computed in bfloat16, one precision
below the configuration's float32, put in the program's place, fails each
cell's comparison, while the reference against itself passes."""
import jax.numpy as jnp
import pytest

from chipbench import compare, harness, testing, world

CELLS = ("paper-noma.mapel-gwmin", "paper-noma.online-update-aware",
         "ota.seed-sweep8", "paper-noma.cell-sweep-4chip")


@pytest.mark.parametrize("name", CELLS)
def test_bfloat16_control_is_not_correct(name):
    cell = testing.tiny_cell(name)
    fl = {**cell.config["fl"], **cell.traffic["fl"]}
    w = world.build_world(cell.config, 11)
    control = harness.reference(cell).run_instance(
        w, fl, cell.config["cell"], 11, dtype=jnp.bfloat16)
    want = harness.run_reference(cell, w, fl, 11, control)
    ok, checks = compare.verdict(compare.numbers(control, want),
                                 cell.spec["limits"])
    assert not ok, checks
    plain = harness.reference(cell).run_instance(w, fl, cell.config["cell"],
                                                 11)
    ok, checks = compare.verdict(
        compare.numbers(plain, harness.run_reference(cell, w, fl, 11, plain)),
        cell.spec["limits"])
    assert ok, checks
