"""The FLOP counter's model against the program's own LeNet-300-100."""
import jax

from chipbench import flops


def test_lenet_counts_match_the_program_model():
    from repro.models.fl_models import get_fl_model
    from repro.utils.tree import tree_count

    params = get_fl_model("lenet").init(jax.random.PRNGKey(0))
    assert flops.dense_params() == tree_count(params) == 266_610
    assert flops.dense_macs() == 266_200


def test_horizon_flops_counts_real_samples_and_every_eval():
    sizes = [10, 20, 30, 40]
    got = flops.horizon_flops([(0, 1), (2,), ()], sizes, epochs=2,
                              test_samples=5)
    want = 266_200 * (6 * 2 * (10 + 20 + 30) + 2 * 5 * 3)
    assert got == want
