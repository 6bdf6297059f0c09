"""The FLOP counter, and LeNet-300-100's counts against the program's own
model."""
import jax

from chipbench import flops, harness


def test_lenet_counts_match_the_program_model():
    from repro.models.fl_models import get_fl_model
    from repro.utils.tree import tree_count

    cell = harness.load_cell("paper-noma.mapel-gwmin")
    ref = harness.reference(cell)
    params = get_fl_model("lenet").init(jax.random.PRNGKey(0))
    assert ref.param_count(cell.config) == tree_count(params) == 266_610
    assert ref.sample_flops(cell.config) == (6 * 266_200, 2 * 266_200)


def test_horizon_flops_counts_real_samples_and_every_eval():
    sizes = [10, 20, 30, 40]
    got = flops.horizon_flops([(0, 1), (2,), ()], sizes, epochs=2,
                              test_samples=5, train_flops=7, eval_flops=3)
    assert got == 7 * 2 * (10 + 20 + 30) + 3 * 5 * 3
