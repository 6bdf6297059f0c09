"""The harness on the CPU, with the look for a chip skipped: a sound run of
each cell comes out correct, and a run with a fault planted under the timed
path (``testing.FAULTS``: a round body that returns the parameters it was
given, SGD steps that see half of each minibatch, a first round that names
another device than the scheduler chose) comes out not correct."""
import pytest

from chipbench import testing

CELLS = ("paper-noma.mapel-gwmin", "paper-noma.online-update-aware",
         "ota.seed-sweep8")


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    result = testing.run_tiny(name, seed=2**31 + 5)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("fault", sorted(testing.FAULTS))
@pytest.mark.parametrize("name", CELLS)
def test_fault_is_not_correct(name, fault):
    result = testing.run_tiny(name, seed=7, fault=fault)
    assert not result["correct"], result["checks"]
    assert result["failed"] == result["attempted"]
