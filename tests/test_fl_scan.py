"""Scanned-horizon equality grid: one-lax.scan driver vs the per-round loop.

``cfg.horizon = "scan"`` folds a precomputed-schedule horizon into ONE
device program (fl_engine.run_horizon).  It must reproduce the per-round
batched driver across uplink x compression x policy: identical device
groups, bit-widths, rates, compression ratios and wall times (all computed
from the same host plan), with accuracies equal to f32 tolerance — in
practice bit-identical, since the scan body is the same
``_train_quantize_aggregate`` jitted computation.  Also pinned here: the
T*K > M empty-tail padding (zero agg weights multiply padded rows out of
the aggregate exactly), the vmapped seed sweep's row-0 identity, the
shard_map'd cell sweep (on multi-device hosts), the client-sampled eval
plan shared by both drivers, and the untraced-online-policy rejection
(traced-protocol policies run under the scan — tests/test_policy_scan.py).
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.config import FLConfig
from repro.core import channel, fl
from repro.data import dirichlet_partition, make_mnist_like

M = 12


@pytest.fixture(scope="module")
def world():
    ds = make_mnist_like(num_samples=800, seed=0)
    cell = channel.CellConfig(num_devices=M)
    shards = dirichlet_partition(ds.y_train, M, seed=0)
    return ds, cell, shards


@pytest.fixture(scope="module")
def tiny_world():
    """4-device cell so a 3-round, K=2 horizon exhausts the device set."""
    ds = make_mnist_like(num_samples=400, seed=0)
    cell = channel.CellConfig(num_devices=4)
    shards = dirichlet_partition(ds.y_train, 4, seed=0)
    return ds, cell, shards


def _cfg(*, m=M, group_size=3, rounds=3, scheduler="lazy-gwmin",
         compression="adaptive", horizon="per-round", eval_sample=1.0,
         seed=0):
    return FLConfig(num_devices=m, group_size=group_size, num_rounds=rounds,
                    scheduler=scheduler, power_mode="max",
                    compression=compression, fl_engine="batched",
                    horizon=horizon, eval_sample=eval_sample, seed=seed)


def _run(world, cfg, *, uplink="noma", eval_every=1):
    ds, cell, shards = world
    return fl.run_federated_learning(ds, shards, cell, cfg, uplink=uplink,
                                     eval_every=eval_every)


def _assert_equal_runs(a, b, *, acc_atol=0.0):
    """Scan vs per-round: schedules/bits/rates/ratios/times must be
    identical (same host plan, same traced bits); accuracies bit-equal by
    default — both drivers run the same jitted eval computation."""
    assert [l.devices for l in a.logs] == [l.devices for l in b.logs]
    for la, lb in zip(a.logs, b.logs):
        np.testing.assert_array_equal(la.bits, lb.bits)
        np.testing.assert_array_equal(la.rates, lb.rates)
        np.testing.assert_array_equal(la.compression_ratios,
                                      lb.compression_ratios)
    np.testing.assert_array_equal(a.times(), b.times())
    np.testing.assert_allclose(a.accuracies(), b.accuracies(), atol=acc_atol)
    for x, y in zip(jax.tree_util.tree_leaves(a.final_params),
                    jax.tree_util.tree_leaves(b.final_params)):
        d = np.abs(np.asarray(x, np.float64) - np.asarray(y, np.float64))
        assert d.mean() < 1e-6, f"mean param drift {d.mean()}"
        assert d.max() < 2e-2, f"max param drift {d.max()}"


# lazy-gwmin: the paper's precomputed MWIS policy; random: the §IV baseline
# with its own PRNG stream — both precomputed, both must plan identically
# in either driver
@pytest.mark.parametrize("scheduler", ["lazy-gwmin", "random"])
@pytest.mark.parametrize("compression", ["adaptive", "none"])
@pytest.mark.parametrize("uplink", ["noma", "tdma"])
def test_scan_equality_grid(world, uplink, compression, scheduler):
    per_round = _run(world, _cfg(compression=compression,
                                 scheduler=scheduler), uplink=uplink)
    scanned = _run(world, _cfg(compression=compression, scheduler=scheduler,
                               horizon="scan"), uplink=uplink)
    _assert_equal_runs(per_round, scanned)


@pytest.mark.parametrize("scheduler", ["round-robin", "lazy-gwmin"])
@pytest.mark.parametrize("uplink", ["noma", "tdma"])
def test_scan_equality_empty_tail_rounds(tiny_world, uplink, scheduler):
    """T*K > M schedules end in short/empty groups; the scan pads them with
    zero-weight rows and must log them identically (no training, wall
    clock still advances)."""
    kw = dict(m=4, group_size=2, rounds=3, scheduler=scheduler)
    per_round = _run(tiny_world, _cfg(**kw), uplink=uplink)
    scanned = _run(tiny_world, _cfg(horizon="scan", **kw), uplink=uplink)
    if scheduler == "round-robin":
        assert scanned.logs[-1].devices == ()
        assert scanned.logs[-1].bits.size == 0
    _assert_equal_runs(per_round, scanned)


def test_scan_eval_every_forward_fill(world):
    """eval_every > 1: the scan skips those rounds' eval inside the program
    (lax.cond -> NaN) and the host forward-fills — same repeated-accuracy
    logs as the per-round driver, final round always evaluated."""
    per_round = _run(world, _cfg(rounds=4), eval_every=3)
    scanned = _run(world, _cfg(rounds=4, horizon="scan"), eval_every=3)
    _assert_equal_runs(per_round, scanned)
    accs = scanned.accuracies()
    assert accs[1] == accs[0] and accs[2] == accs[0]  # forward-filled
    assert not np.isnan(accs).any()


def test_scan_eval_sample_matches_per_round(world):
    """Client-sampled eval: both drivers consume the same (T, n) plan, so
    the sampled accuracies are bit-identical too."""
    per_round = _run(world, _cfg(eval_sample=0.5))
    scanned = _run(world, _cfg(eval_sample=0.5, horizon="scan"))
    _assert_equal_runs(per_round, scanned)


def test_vmapped_seeds_row0_matches_single(world):
    """Row s of the vmapped sweep is the same program run_horizon_scanned
    runs for that seed alone — row 0 must be bit-identical to the
    single-seed run, and different seeds must actually differ."""
    ds, cell, shards = world
    cfg = _cfg(horizon="scan")
    single = fl.run_federated_learning(ds, shards, cell, cfg)
    sweep = fl.run_horizon_vmapped(ds, shards, cell, cfg, seeds=[0, 1, 2])
    assert len(sweep) == 3
    r0 = sweep[0]
    assert [l.devices for l in r0.logs] == [l.devices for l in single.logs]
    np.testing.assert_array_equal(r0.accuracies(), single.accuracies())
    np.testing.assert_array_equal(r0.times(), single.times())
    for x, y in zip(jax.tree_util.tree_leaves(r0.final_params),
                    jax.tree_util.tree_leaves(single.final_params)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    # seeds are real: some other row differs from seed 0 somewhere
    assert any(
        [l.devices for l in sweep[s].logs] != [l.devices for l in r0.logs]
        or not np.array_equal(sweep[s].accuracies(), r0.accuracies())
        for s in (1, 2)
    )


def test_cell_sweep_matches_individual_scans(tiny_world):
    """Each (cell, seed) instance of the sweep grid equals the standalone
    scanned run at that instance's seed (1-device mesh here; the sharded
    test below pins multi-device meshes against this program)."""
    ds, cell, shards = tiny_world
    cfg = _cfg(m=4, group_size=2, rounds=3, horizon="scan")
    grid = fl.run_cell_sweep(ds, shards, cell, cfg, num_cells=2,
                             seeds_per_cell=2)
    for c in range(2):
        for s in range(2):
            inst = fl.run_federated_learning(
                ds, shards, cell, dataclasses.replace(cfg, seed=c * 2 + s))
            assert ([l.devices for l in grid[c][s].logs]
                    == [l.devices for l in inst.logs])
            np.testing.assert_array_equal(grid[c][s].accuracies(),
                                          inst.accuracies())
            np.testing.assert_array_equal(grid[c][s].times(), inst.times())


@pytest.mark.skipif(jax.local_device_count() < 2,
                    reason="needs >= 2 local devices "
                           "(XLA_FLAGS=--xla_force_host_platform_device_count)")
def test_cell_sweep_sharded_matches_single_mesh(tiny_world):
    """shard_map over the cell mesh (including the C-padding path: C=3 on
    a 2-shard mesh) must equal the trivial 1-device-mesh program."""
    ds, cell, shards = tiny_world
    cfg = _cfg(m=4, group_size=2, rounds=3, horizon="scan")
    base = fl.run_cell_sweep(ds, shards, cell, cfg, num_cells=3,
                             seeds_per_cell=2)
    sharded = fl.run_cell_sweep(ds, shards, cell, cfg, num_cells=3,
                                seeds_per_cell=2, cell_shards=2)
    for c in range(3):
        for s in range(2):
            assert ([l.devices for l in base[c][s].logs]
                    == [l.devices for l in sharded[c][s].logs])
            np.testing.assert_array_equal(base[c][s].accuracies(),
                                          sharded[c][s].accuracies())


def test_cell_sweep_rejects_more_shards_than_devices(tiny_world):
    """Asking for more cell shards than local devices raises with both
    counts; it never runs on fewer devices in silence."""
    ds, cell, shards = tiny_world
    cfg = _cfg(m=4, group_size=2, rounds=2, horizon="scan")
    n = jax.local_device_count() + 1
    with pytest.raises(
        ValueError,
        match=(f"cell_shards={n} asks for {n} devices, but only "
               f"{n - 1} local devices are present"),
    ):
        fl.run_cell_sweep(ds, shards, cell, cfg, num_cells=2,
                          seeds_per_cell=1, cell_shards=n)


def _register_untraced_online():
    """A registered online policy WITHOUT the traced protocol — the
    rejection case since the built-in online policies all gained
    ``traced_protocol`` (PR 10).  Callers pop it in a finally block."""
    from repro.core import scheduling

    @scheduling.register_policy("test-untraced-online")
    class UntracedOnline(scheduling._ScoreTopKPolicy):
        traced_protocol = False

    return scheduling


def test_scan_accepts_traced_online_policies_at_config_time():
    """The built-in online policies carry the traced protocol, so
    horizon='scan' now accepts them (the equality grid in
    test_policy_scan.py pins the semantics)."""
    for name in ("update-aware", "age-fair", "matching-pursuit"):
        kw = (dict(uplink="ota", compression="none")
              if name == "matching-pursuit" else {})
        cfg = FLConfig(num_devices=4, group_size=2, num_rounds=2,
                       scheduler=name, horizon="scan", power_mode="max",
                       **kw)
        assert cfg.horizon == "scan"


def test_scan_rejects_untraced_online_policy_at_config_time():
    scheduling = _register_untraced_online()
    try:
        with pytest.raises(
            ValueError,
            match="horizon='scan' cannot drive online policy",
        ):
            FLConfig(num_devices=4, group_size=2, num_rounds=2,
                     scheduler="test-untraced-online", horizon="scan")
    finally:
        scheduling._REGISTRY.pop("test-untraced-online", None)


def test_scan_rejects_untraced_online_policy_called_directly(tiny_world):
    """run_horizon_scanned called with a per-round config must raise the
    same error rather than silently planning an offline schedule."""
    ds, cell, shards = tiny_world
    scheduling = _register_untraced_online()
    try:
        cfg = _cfg(m=4, group_size=2, rounds=2,
                   scheduler="test-untraced-online")
        with pytest.raises(
            ValueError,
            match="horizon='scan' cannot drive online policy",
        ):
            fl.run_horizon_scanned(ds, shards, cell, cfg)
    finally:
        scheduling._REGISTRY.pop("test-untraced-online", None)


def test_scan_online_rejects_mapel_at_config_time():
    """MAPEL's polyblock search is host-iterative: the traced round body
    cannot run it, so the scan + online + mapel combo is rejected up
    front with its own pinned message."""
    with pytest.raises(ValueError, match="cannot use power_mode='mapel'"):
        FLConfig(num_devices=4, group_size=2, num_rounds=2,
                 scheduler="update-aware", power_mode="mapel",
                 horizon="scan")


def test_scan_online_rejects_mapel_called_directly(tiny_world):
    ds, cell, shards = tiny_world
    cfg = FLConfig(num_devices=4, group_size=2, num_rounds=2,
                   scheduler="update-aware", power_mode="mapel",
                   fl_engine="batched", seed=0)
    with pytest.raises(ValueError, match="cannot use power_mode='mapel'"):
        fl.run_horizon_scanned(ds, shards, cell, cfg)


def test_unknown_horizon_rejected_at_config_time():
    with pytest.raises(ValueError, match="unknown horizon"):
        FLConfig(num_devices=4, group_size=2, num_rounds=2,
                 horizon="time-travel")


# --------------------------------------------------------------------------
# Model-agnostic payloads through the scanned horizon
# --------------------------------------------------------------------------


@pytest.fixture(scope="module")
def token_world():
    from repro.data.tokens import make_token_dataset

    ds = make_token_dataset(vocab_size=64, num_samples=400, seq_len=8,
                            seed=0)
    cell = channel.CellConfig(num_devices=M)
    shards = dirichlet_partition(ds.class_train, M, seed=0)
    return ds, cell, shards


def _model_cfg(*, horizon, model="tiny-transformer", topk=1.0, m=M,
               group_size=3, rounds=3):
    return FLConfig(num_devices=m, group_size=group_size, num_rounds=rounds,
                    learning_rate=0.05, batch_size=8,
                    scheduler="lazy-gwmin", power_mode="max",
                    compression="adaptive", fl_engine="batched",
                    horizon=horizon, model=model, topk=topk, seed=0)


@pytest.mark.parametrize("uplink", ["noma", "tdma"])
def test_scan_equality_grid_transformer(token_world, uplink):
    """scan vs per-round on a tiny registry transformer: identical
    schedules/bits/rates/ratios/times, bit-equal accuracies — the same
    contract the LeNet grid pins, now on a token payload."""
    per_round = _run(token_world, _model_cfg(horizon="per-round"),
                     uplink=uplink)
    scanned = _run(token_world, _model_cfg(horizon="scan"), uplink=uplink)
    _assert_equal_runs(per_round, scanned)


def test_scan_topk_matches_per_round(token_world):
    """The top-k ∘ DoReFa stage runs inside the scan body too: same traced
    (kept, bits) plans, same sparse on-air ratios, bit-equal accuracies."""
    per_round = _run(token_world, _model_cfg(horizon="per-round", topk=0.1))
    scanned = _run(token_world, _model_cfg(horizon="scan", topk=0.1))
    _assert_equal_runs(per_round, scanned)
    # the stage is actually on: ratios exceed the dense-at-these-bits value
    assert all(np.all(l.compression_ratios > 1.0)
               for l in scanned.logs if l.bits.size)


def test_transformer_class_payload_topk_batched_and_scan():
    """Acceptance pin: a >= 10^6-param transformer payload runs through
    BOTH the batched per-round engine and the scanned horizon with
    top-k + DoReFa, and the two agree bit for bit."""
    from repro.data.tokens import make_token_dataset
    from repro.models.fl_models import get_fl_model
    from repro.utils.tree import tree_count

    model = get_fl_model("tiny-transformer-1m")
    params = model.init(jax.random.PRNGKey(0))
    assert tree_count(params) >= 1_000_000

    ds = make_token_dataset(vocab_size=model.cfg.vocab_size,
                            num_samples=200, seq_len=8, seed=0)
    cell = channel.CellConfig(num_devices=6)
    shards = dirichlet_partition(ds.class_train, 6, seed=0)
    cfg = _model_cfg(horizon="per-round", model="tiny-transformer-1m",
                     topk=0.01, m=6, group_size=2, rounds=2)
    per_round = fl.run_federated_learning(ds, shards, cell, cfg)
    scanned = fl.run_federated_learning(
        ds, shards, cell, dataclasses.replace(cfg, horizon="scan"))
    _assert_equal_runs(per_round, scanned)
    # at 1% top-k the honest on-air ratio is large and the §IV clamp never
    # reports the meaningless dense r = 1
    assert all(np.all(l.compression_ratios > 5.0)
               for l in scanned.logs if l.bits.size)
