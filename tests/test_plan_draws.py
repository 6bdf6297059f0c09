"""An instance's random draws as one program (``fl._instance_draws``): bit
for bit the eager formulas the drivers ran before, and one read back per
instance planned, whatever the driver."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import FLConfig
from repro.core import channel as chan
from repro.core import fl, ota
from repro.data import dirichlet_partition, make_mnist_like
from repro.models.fl_models import get_fl_model
from repro.utils import spans
from repro.utils.tree import tree_count

# the benchmark's seeds lie above 2**31: PRNGKey of a Python int that large
# must be what the program's integer operand gives
SEEDS = (7, 9100011, 2300000011, 3100000024)


def _one_round_gains(key, dist, cell):
    """One round's h = L(d) |h0|, as the channel module drew it per round."""
    kr, ki = jax.random.split(key)
    re = jax.random.normal(kr, dist.shape) * jnp.sqrt(0.5)
    im = jax.random.normal(ki, dist.shape) * jnp.sqrt(0.5)
    return chan.large_scale_gain(dist, cell) * jnp.sqrt(re**2 + im**2)


def _eager_draws(seed, cell, model, num_rounds):
    """The draws as the drivers made them eagerly: the model's init, the
    positions and the round channels vmapped over per-round keys, the
    noise keys folded and read back one round at a time, the downlink on
    L(d)."""
    key = jax.random.PRNGKey(seed)
    params = model.init(key)
    dist = chan.sample_positions(jax.random.fold_in(key, 1), cell)
    round_keys = jax.random.split(jax.random.fold_in(key, 2), num_rounds)
    gains = np.asarray(jax.vmap(_one_round_gains, in_axes=(0, None, None))(
        round_keys, dist, cell))
    np.testing.assert_array_equal(np.asarray(chan.sample_round_channels(
        jax.random.fold_in(key, 2), dist, cell, num_rounds)), gains)
    base = jax.random.PRNGKey(seed + ota.OTA_SEED_OFFSET)
    keys = np.stack([np.asarray(jax.random.fold_in(base, t))
                     for t in range(num_rounds)])
    dl_time = float(chan.downlink_time_seconds(
        tree_count(params) * 32, chan.large_scale_gain(dist, cell), cell))
    return params, np.asarray(dist), gains, keys, dl_time


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("model_name,m,t", [
    ("lenet", 300, 35),                      # the paper cell
    ("lenet", 6, 3),
    ("granite-h-micro-lora:smoke", 6, 3),    # LoRA adapters: uniform A, zero B
])
def test_instance_draws_equal_the_eager_formulas(model_name, m, t, seed):
    model = get_fl_model(model_name)
    cell = chan.CellConfig(num_devices=m)
    params, dist, gains, keys, dl_time = _eager_draws(seed, cell, model, t)

    draws = fl._instance_draws(seed, cell, model, t)
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(draws.params0), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert draws.payload == tree_count(params) * 32
    np.testing.assert_array_equal(draws.gains, gains)
    np.testing.assert_array_equal(draws.noise_keys, keys)
    assert draws.noise_keys.dtype == np.uint32
    assert draws.dl_time == dl_time
    np.testing.assert_array_equal(ota.horizon_keys(seed, t), keys)

    u, _, _, _ = fl._draw_program(
        np.array([seed, seed + ota.OTA_SEED_OFFSET], np.int64),
        num_devices=m, num_rounds=t)
    np.testing.assert_array_equal(
        np.asarray(chan.distances_from_uniforms(u, cell)), dist)


M = 6


@pytest.fixture(scope="module")
def world():
    ds = make_mnist_like(num_samples=300, seed=0)
    cell = chan.CellConfig(num_devices=M)
    shards = dirichlet_partition(ds.y_train, M, seed=0)
    return ds, cell, shards


@pytest.mark.parametrize("driver,scheduler,seeds", [
    ("scanned", "lazy-gwmin", (2300000011,)),
    ("scanned", "update-aware", (2300000011,)),       # the online setup
    ("vmapped", "lazy-gwmin", (7, 2300000011, 3100000024)),
    ("vmapped", "update-aware", (7, 3100000024)),
    ("per-round", "lazy-gwmin", (9100011,)),
])
def test_plan_device_reads_count_one_per_instance(world, driver, scheduler,
                                                  seeds):
    ds, cell, shards = world
    cfg = FLConfig(num_devices=M, group_size=2, num_rounds=2,
                   scheduler=scheduler, power_mode="max",
                   compression="adaptive", fl_engine="batched",
                   horizon="per-round" if driver == "per-round" else "scan",
                   seed=seeds[0])
    before = spans.counts().get("plan.device_reads", 0)
    if driver == "vmapped":
        fl.run_horizon_vmapped(ds, shards, cell, cfg, seeds=seeds)
    elif driver == "scanned":
        fl.run_horizon_scanned(ds, shards, cell, cfg)
    else:
        fl.run_federated_learning(ds, shards, cell, cfg)
    assert spans.counts()["plan.device_reads"] - before == len(seeds)
