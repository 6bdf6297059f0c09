"""End-to-end behaviour tests for the paper's system (FL over NOMA)."""
import jax
import numpy as np
import pytest

from repro.config import FLConfig
from repro.core import channel, fl
from repro.data import dirichlet_partition, make_mnist_like

M = 30  # small cell for test speed


@pytest.fixture(scope="module")
def small_world():
    ds = make_mnist_like(num_samples=2500, seed=0)
    cell = channel.CellConfig(num_devices=M)
    shards = dirichlet_partition(ds.y_train, M, seed=0)
    return ds, cell, shards


def _run(ds, cell, shards, *, rounds=8, scheduler="lazy-gwmin",
         power="max", uplink="noma", compression="adaptive", seed=0):
    cfg = FLConfig(num_devices=M, group_size=3, num_rounds=rounds,
                   scheduler=scheduler, power_mode=power,
                   compression=compression, seed=seed)
    return fl.run_federated_learning(ds, shards, cell, cfg, uplink=uplink)


def test_fl_accuracy_improves(small_world):
    ds, cell, shards = small_world
    res = _run(ds, cell, shards, rounds=10)
    accs = res.accuracies()
    assert accs[-1] > 0.3, f"final accuracy too low: {accs[-1]}"
    assert accs[-1] > accs[0]


def test_constraint_c1_each_device_once(small_world):
    ds, cell, shards = small_world
    res = _run(ds, cell, shards, rounds=8)
    seen = [d for log in res.logs for d in log.devices]
    assert len(seen) == len(set(seen))


def test_noma_rounds_faster_than_tdma(small_world):
    """Paper §IV: NOMA round = t + T_d, TDMA round = K*t + T_d."""
    ds, cell, shards = small_world
    noma_res = _run(ds, cell, shards, rounds=4, uplink="noma")
    tdma_res = _run(ds, cell, shards, rounds=4, uplink="tdma")
    t_noma = np.diff(noma_res.times())
    t_tdma = np.diff(tdma_res.times())
    # identical downlink; uplink is K x longer for TDMA
    np.testing.assert_allclose(
        t_tdma - t_noma, (3 - 1) * cell.slot_seconds, rtol=1e-6)


def test_adaptive_bits_recorded_and_bounded(small_world):
    ds, cell, shards = small_world
    # not 5 or 9: tests/test_sanitizers.py counts compiles at those sizes
    res = _run(ds, cell, shards, rounds=4)
    for log in res.logs:
        assert np.all(log.bits >= 1) and np.all(log.bits <= 32)
        assert np.all(log.compression_ratios >= 1.0)


def test_tdma_adaptive_compression_uses_subslot_budget(small_world):
    """Regression: adaptive compression used to be silently skipped for TDMA
    (uploads forced to 32 bits), biasing the NOMA-vs-TDMA comparison.  Each
    TDMA device now quantizes to its own interference-free sub-slot budget."""
    ds, cell, shards = small_world
    res = _run(ds, cell, shards, rounds=3, uplink="tdma")
    bits = np.concatenate([log.bits for log in res.logs])
    assert np.all((bits >= 1) & (bits <= 32))
    assert np.any(bits < 32), "TDMA budgets here are compressive; 32 = skipped"
    ratios = np.concatenate([log.compression_ratios for log in res.logs])
    assert np.all(ratios >= 1.0)


def test_tdma_compression_none_stays_full_precision(small_world):
    ds, cell, shards = small_world
    res = _run(ds, cell, shards, rounds=2, uplink="tdma", compression="none")
    for log in res.logs:
        assert np.all(log.bits == 32)


def test_deterministic_given_seed(small_world):
    ds, cell, shards = small_world
    r1 = _run(ds, cell, shards, rounds=3, seed=5)
    r2 = _run(ds, cell, shards, rounds=3, seed=5)
    np.testing.assert_array_equal(r1.accuracies(), r2.accuracies())
    assert [l.devices for l in r1.logs] == [l.devices for l in r2.logs]


@pytest.fixture(scope="module")
def tiny_world():
    """4-device cell so a 3-round, K=2 horizon exhausts the device set."""
    ds = make_mnist_like(num_samples=400, seed=0)
    cell = channel.CellConfig(num_devices=4)
    shards = dirichlet_partition(ds.y_train, 4, seed=0)
    return ds, cell, shards


@pytest.mark.parametrize("uplink", ["noma", "tdma"])
@pytest.mark.parametrize("scheduler", ["round-robin", "proportional-fair"])
def test_fl_survives_empty_tail_rounds(tiny_world, uplink, scheduler):
    """Regression: T*K > M schedules produce empty tail groups; aggregation
    used to crash (``tree_map`` over zero deltas).  Empty rounds must skip
    training/aggregation but still advance the wall clock and be logged."""
    ds, cell, shards = tiny_world
    cfg = FLConfig(num_devices=4, group_size=2, num_rounds=3,
                   scheduler=scheduler, power_mode="max",
                   compression="adaptive", seed=0)
    res = fl.run_federated_learning(ds, shards, cell, cfg, uplink=uplink)
    assert len(res.logs) == 3
    assert res.logs[-1].devices == ()
    assert res.logs[-1].bits.size == 0 and res.logs[-1].rates.size == 0
    times = res.times()
    assert np.all(np.diff(times) > 0), "empty rounds must still take time"
    assert np.isfinite(res.logs[-1].test_accuracy)
    # the empty round leaves the model untouched: same accuracy as round 1
    assert res.logs[-1].test_accuracy == res.logs[-2].test_accuracy


def test_final_round_eval_fresh_when_eval_every_skips_it(tiny_world):
    """Regression: with eval_every > 1 and num_rounds - 1 not a multiple,
    the final round used to copy the last (stale) eval instead of measuring
    the final model — FLResult.accuracies()[-1] lied about the run's
    outcome.  The last round must always be evaluated."""
    ds, cell, shards = tiny_world
    from repro.models import lenet

    cfg = FLConfig(num_devices=4, group_size=2, num_rounds=4,
                   scheduler="age-fair", power_mode="max",
                   compression="adaptive", seed=0)
    res = fl.run_federated_learning(ds, shards, cell, cfg, eval_every=2)
    fresh = float(lenet.accuracy(
        res.final_params, np.asarray(ds.x_test), np.asarray(ds.y_test)))
    assert res.logs[-1].test_accuracy == fresh
    # intermediate skipped rounds still carry the previous eval forward
    assert res.logs[1].test_accuracy == res.logs[0].test_accuracy


def test_tdma_empty_tail_round_charges_no_uplink_airtime(tiny_world):
    """Regression: an empty T*K > M tail round under TDMA used to charge
    group_size * slot_seconds of uplink airtime with zero transmitting
    devices, skewing the Fig. 5 time axis.  Airtime is len(devs) sub-slots."""
    ds, cell, shards = tiny_world
    cfg = FLConfig(num_devices=4, group_size=2, num_rounds=3,
                   scheduler="round-robin", power_mode="max",
                   compression="adaptive", seed=0)
    res = fl.run_federated_learning(ds, shards, cell, cfg, uplink="tdma")
    assert res.logs[-1].devices == ()
    per_round = np.diff(np.concatenate([[0.0], res.times()]))
    # full rounds: 2 sub-slots + downlink; empty tail: downlink only
    np.testing.assert_allclose(
        per_round[-1], per_round[0] - 2 * cell.slot_seconds, rtol=1e-9)


def test_noma_empty_tail_round_charges_no_uplink_airtime(tiny_world):
    """The shared NOMA uplink slot is only spent when someone transmits: an
    empty tail round costs the downlink broadcast only (keeping the NOMA
    and TDMA time axes consistent on empty rounds)."""
    ds, cell, shards = tiny_world
    cfg = FLConfig(num_devices=4, group_size=2, num_rounds=3,
                   scheduler="round-robin", power_mode="max",
                   compression="adaptive", seed=0)
    res = fl.run_federated_learning(ds, shards, cell, cfg, uplink="noma")
    assert res.logs[-1].devices == ()
    per_round = np.diff(np.concatenate([[0.0], res.times()]))
    np.testing.assert_allclose(
        per_round[-1], per_round[0] - cell.slot_seconds, rtol=1e-9)


def test_tdma_partial_tail_round_charges_len_devs_subslots(tiny_world):
    """A partial tail group (1 of K=3 devices left) is charged 1 sub-slot,
    not K."""
    ds, cell, shards = tiny_world
    cfg = FLConfig(num_devices=4, group_size=3, num_rounds=2,
                   scheduler="round-robin", power_mode="max",
                   compression="adaptive", seed=0)
    res = fl.run_federated_learning(ds, shards, cell, cfg, uplink="tdma")
    assert len(res.logs[0].devices) == 3 and len(res.logs[1].devices) == 1
    per_round = np.diff(np.concatenate([[0.0], res.times()]))
    np.testing.assert_allclose(
        per_round[1], per_round[0] - 2 * cell.slot_seconds, rtol=1e-9)


def test_scheduler_weighted_rate_ordering(small_world):
    """Greedy MWIS schedule achieves >= weighted sum rate of random/RR."""
    ds, cell, shards = small_world
    from repro.core import scheduling

    sizes = np.array([len(s) for s in shards], float)
    weights = sizes / sizes.sum()
    key = jax.random.PRNGKey(0)
    dist = channel.sample_positions(key, cell)
    gains = np.asarray(channel.sample_round_channels(
        jax.random.fold_in(key, 2), dist, cell, 4))   # not 5: see above
    g = scheduling.lazy_greedy_schedule(
        gains, weights, 3, pmax=cell.max_power_w,
        noise_power=cell.noise_power_w)
    r = scheduling.random_schedule(
        np.random.default_rng(0), gains, weights, 3,
        pmax=cell.max_power_w, noise_power=cell.noise_power_w)
    rr = scheduling.round_robin_schedule(
        gains, weights, 3, pmax=cell.max_power_w,
        noise_power=cell.noise_power_w)
    assert g.weighted_sum_rate >= r.weighted_sum_rate
    assert g.weighted_sum_rate >= rr.weighted_sum_rate


@pytest.mark.parametrize("scheduler", ["update-aware", "age-fair"])
def test_online_policies_run_live_and_revisit(tiny_world, scheduler):
    """Online policies select inside the training loop: with T*K > M they
    revisit devices instead of emitting empty tail rounds, and the whole
    run stays deterministic given the seed."""
    ds, cell, shards = tiny_world
    cfg = FLConfig(num_devices=4, group_size=2, num_rounds=3,
                   scheduler=scheduler, power_mode="max",
                   compression="adaptive", seed=0)
    res = fl.run_federated_learning(ds, shards, cell, cfg)
    assert len(res.logs) == 3
    assert all(len(log.devices) == 2 for log in res.logs)
    seen = [d for log in res.logs for d in log.devices]
    assert len(seen) == 6
    assert len(seen) > len(set(seen))                # some device revisited
    assert all(0 <= d < 4 for d in seen)
    assert np.isfinite(res.logs[-1].test_accuracy)
    for log in res.logs:                              # live rounds upload
        assert np.all(log.bits >= 1) and np.all(log.bits <= 32)
    r2 = fl.run_federated_learning(ds, shards, cell, cfg)
    assert [l.devices for l in res.logs] == [l.devices for l in r2.logs]
    np.testing.assert_array_equal(res.accuracies(), r2.accuracies())


def test_online_policy_live_tdma_uplink(tiny_world):
    ds, cell, shards = tiny_world
    cfg = FLConfig(num_devices=4, group_size=2, num_rounds=2,
                   scheduler="age-fair", power_mode="max",
                   compression="adaptive", seed=0)
    res = fl.run_federated_learning(ds, shards, cell, cfg, uplink="tdma")
    assert len(res.logs) == 2
    assert all(len(log.devices) == 2 for log in res.logs)


def test_precomputed_policies_unchanged_by_registry_path(small_world):
    """fl.make_schedule now resolves through the registry; the precomputed
    path must keep producing the same schedules the FL loop consumed before
    the redesign (spot-check: same devices for the same seed/config)."""
    ds, cell, shards = small_world
    from repro.core import scheduling

    cfg = FLConfig(num_devices=M, group_size=3, num_rounds=4,
                   scheduler="random", power_mode="max",
                   compression="adaptive", seed=5)
    key = jax.random.PRNGKey(cfg.seed)
    dist = channel.sample_positions(jax.random.fold_in(key, 1), cell)
    gains = np.asarray(channel.sample_round_channels(
        jax.random.fold_in(key, 2), dist, cell, cfg.num_rounds))
    sizes = np.array([len(s) for s in shards], float)
    weights = sizes / sizes.sum()
    via_registry = fl.make_schedule(gains, weights, cell, cfg)
    direct = scheduling.random_schedule(
        np.random.default_rng(cfg.seed + 17), gains, weights, 3,
        power_mode="max", pmax=cell.max_power_w,
        noise_power=cell.noise_power_w)
    assert via_registry.rounds == direct.rounds
    for pa, pb in zip(via_registry.powers, direct.powers):
        np.testing.assert_array_equal(pa, pb)


def test_caller_supplied_online_schedule_accepted(tiny_world):
    """Regression: a Schedule built offline from an online policy revisits
    devices; run_federated_learning must honor the schedule's own
    allow_revisits flag (set by build_schedule) instead of crashing on C1."""
    ds, cell, shards = tiny_world
    from repro.core import scheduling

    cfg = FLConfig(num_devices=4, group_size=2, num_rounds=3,
                   scheduler="age-fair", power_mode="max",
                   compression="adaptive", seed=0)
    key = jax.random.PRNGKey(cfg.seed)
    dist = channel.sample_positions(jax.random.fold_in(key, 1), cell)
    gains = np.asarray(channel.sample_round_channels(
        jax.random.fold_in(key, 2), dist, cell, cfg.num_rounds))
    sizes = np.array([len(s) for s in shards], float)
    weights = sizes / sizes.sum()
    sched = scheduling.build_schedule(
        scheduling.get_policy("age-fair"), gains, weights,
        fl.policy_config(cell, cfg))
    assert sum(len(g) for g in sched.rounds) > 4     # revisits present
    res = fl.run_federated_learning(ds, shards, cell, cfg, schedule=sched)
    assert [l.devices for l in res.logs] == [tuple(g) for g in sched.rounds]
