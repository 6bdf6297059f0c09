"""OTA analog-aggregation equality grid (core/ota.py + the three drivers).

What must hold, and where each guarantee comes from:

  * ``superpose_tree`` is THE aggregation operator — the batched per-round
    engine, the scanned horizon and the legacy oracle all call the same
    jitted computation, so a fixed delta stack aggregates bit-identically
    no matter which driver asked.
  * noise_std=0, threshold=0 makes the OTA estimate the exact weighted
    FedAvg aggregate (allclose, not bit-equal: the receiver renormalizes
    by the f32 participant weight sum).
  * the Pallas fused scale+superpose+denoise kernel equals the XLA einsum
    oracle, including K=0 (bare noise floor), K=1, and the chunked slab
    path.
  * scanned-horizon and per-round batched OTA runs are END-TO-END
    bit-identical (same traced round body, same host-folded noise keys);
    the legacy oracle agrees to f32 tolerance (its per-device SGD loop
    accumulates in a different order).
  * receiver noise is reproducible from (seed, round) alone and
    decorrelated across rounds; truncation drops sub-threshold channels.
  * FLConfig rejects incoherent OTA combinations with pinned messages.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import FLConfig
from repro.core import channel, fl, ota, power, scheduling
from repro.data import dirichlet_partition, make_mnist_like
from repro.kernels.aggregate import TILE_ELEMS, ota_aggregate_pallas

M = 8
PMAX = 0.01


@pytest.fixture(scope="module")
def world():
    ds = make_mnist_like(num_samples=400, seed=0)
    cell = channel.CellConfig(num_devices=M)
    shards = dirichlet_partition(ds.y_train, M, seed=0)
    return ds, cell, shards


def _cfg(**kw):
    base = dict(num_devices=M, group_size=3, num_rounds=3, power_mode="max",
                compression="none", fl_engine="batched", uplink="ota",
                eval_sample=1.0, seed=3)
    base.update(kw)
    return FLConfig(**base)


def _run(world, cfg, **kw):
    ds, cell, shards = world
    return fl.run_federated_learning(ds, shards, cell, cfg, **kw)


def _delta_stack(k=4, sizes=((7, 5), (11,)), seed=0):
    rng = np.random.default_rng(seed)
    return {
        f"leaf{i}": jnp.asarray(
            rng.standard_normal((k, *s)).astype(np.float32))
        for i, s in enumerate(sizes)
    }


# --------------------------------------------------------------------------
# The shared operator: exactness, truncation, noise stream
# --------------------------------------------------------------------------

def test_noiseless_superposition_is_exact_weighted_aggregate():
    deltas = _delta_stack()
    gains = jnp.asarray([1e-6, 2e-6, 5e-7, 3e-6], jnp.float32)
    w = np.asarray([0.1, 0.4, 0.3, 0.2])
    key = jnp.asarray(ota.horizon_keys(0, 1)[0])
    out = ota.superpose_tree(deltas, gains, jnp.asarray(w, jnp.float32), key,
                             pmax=PMAX, noise_std=0.0, threshold=0.0)
    for name, leaf in deltas.items():
        expect = np.einsum(
            "k,k...->...", w / w.sum(), np.asarray(leaf, np.float64))
        np.testing.assert_allclose(
            np.asarray(out[name], np.float64), expect, rtol=2e-6, atol=1e-7)


def test_truncation_drops_subthreshold_channels():
    """threshold=0.4: h=[1, 0.5, 0.1, 0.9]*1e-6 vs hmax=1e-6 keeps devices
    {0, 1, 3}; the estimate must be the renormalized aggregate over the
    survivors only — device 2's update must leave no trace."""
    deltas = _delta_stack()
    gains = jnp.asarray([1e-6, 5e-7, 1e-7, 9e-7], jnp.float32)
    w = np.asarray([0.25, 0.25, 0.25, 0.25])
    key = jnp.asarray(ota.horizon_keys(0, 1)[0])
    out = ota.superpose_tree(deltas, gains, jnp.asarray(w, jnp.float32), key,
                             pmax=PMAX, noise_std=0.0, threshold=0.4)
    keep = np.asarray([0, 1, 3])
    for name, leaf in deltas.items():
        arr = np.asarray(leaf, np.float64)
        expect = np.einsum(
            "k,k...->...", w[keep] / w[keep].sum(), arr[keep])
        np.testing.assert_allclose(
            np.asarray(out[name], np.float64), expect, rtol=2e-6, atol=1e-7)


def test_zero_weight_rows_are_padding():
    """agg_w = 0 marks scan-padding rows: they must not participate even
    with the strongest channel (the T*K > M empty-tail contract)."""
    deltas = _delta_stack()
    gains = jnp.asarray([1e-6, 2e-6, 9e-6, 3e-6], jnp.float32)
    w = np.asarray([0.3, 0.3, 0.0, 0.4])
    key = jnp.asarray(ota.horizon_keys(0, 1)[0])
    out = ota.superpose_tree(deltas, gains, jnp.asarray(w, jnp.float32), key,
                             pmax=PMAX, noise_std=0.0, threshold=0.0)
    keep = np.asarray([0, 1, 3])
    arr = np.asarray(deltas["leaf1"], np.float64)
    expect = np.einsum("k,k...->...", w[keep] / w[keep].sum(), arr[keep])
    np.testing.assert_allclose(
        np.asarray(out["leaf1"], np.float64), expect, rtol=2e-6, atol=1e-7)


def test_empty_round_returns_zero_update():
    deltas = _delta_stack()
    gains = jnp.zeros(4, jnp.float32)
    w = jnp.zeros(4, jnp.float32)
    key = jnp.asarray(ota.horizon_keys(0, 1)[0])
    out = ota.superpose_tree(deltas, gains, w, key,
                             pmax=PMAX, noise_std=1e-3, threshold=0.0)
    for leaf in jax.tree_util.tree_leaves(out):
        np.testing.assert_array_equal(np.asarray(leaf), 0.0)


def test_noise_stream_deterministic_and_decorrelated():
    deltas = _delta_stack()
    gains = jnp.asarray([1e-6, 2e-6, 5e-7, 3e-6], jnp.float32)
    w = jnp.asarray([0.25, 0.25, 0.25, 0.25], jnp.float32)
    keys = ota.horizon_keys(7, 2)
    kw = dict(pmax=PMAX, noise_std=1e-8, threshold=0.0)
    a = ota.superpose_tree(deltas, gains, w, jnp.asarray(keys[0]), **kw)
    b = ota.superpose_tree(deltas, gains, w, jnp.asarray(keys[0]), **kw)
    c = ota.superpose_tree(deltas, gains, w, jnp.asarray(keys[1]), **kw)
    clean = ota.superpose_tree(deltas, gains, w, jnp.asarray(keys[0]),
                               pmax=PMAX, noise_std=0.0, threshold=0.0)
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    assert any(
        not np.array_equal(np.asarray(la), np.asarray(lc))
        for la, lc in zip(jax.tree_util.tree_leaves(a),
                          jax.tree_util.tree_leaves(c))
    ), "different rounds must draw different receiver noise"
    assert any(
        not np.array_equal(np.asarray(la), np.asarray(lx))
        for la, lx in zip(jax.tree_util.tree_leaves(a),
                          jax.tree_util.tree_leaves(clean))
    ), "noise_std > 0 must actually perturb the aggregate"
    # and the key schedule itself is a pure function of (seed, T)
    np.testing.assert_array_equal(ota.horizon_keys(7, 2),
                                  ota.horizon_keys(7, 5)[:2])


def test_pallas_operator_matches_einsum_operator():
    deltas = _delta_stack()
    gains = jnp.asarray([1e-6, 2e-6, 5e-7, 3e-6], jnp.float32)
    w = jnp.asarray([0.1, 0.4, 0.3, 0.2], jnp.float32)
    key = jnp.asarray(ota.horizon_keys(1, 1)[0])
    kw = dict(pmax=PMAX, noise_std=1e-8, threshold=0.0)
    xla = ota.superpose_tree(deltas, gains, w, key, **kw)
    pal = ota.superpose_tree(deltas, gains, w, key, use_pallas=True, **kw)
    # The kernel seeds its accumulator with the noise and adds the K terms
    # after it, the einsum adds the noise last: the two summation orders
    # differ by about one f32 ulp, hence a few-ulp relative tolerance.
    for lx, lp in zip(jax.tree_util.tree_leaves(xla),
                      jax.tree_util.tree_leaves(pal)):
        np.testing.assert_allclose(
            np.asarray(lp), np.asarray(lx), rtol=1e-5, atol=1e-9)


# --------------------------------------------------------------------------
# The Pallas kernel vs its einsum oracle (K = 0 / K = 1 / chunked)
# --------------------------------------------------------------------------

def _oracle(flat, coeff, noise):
    return np.einsum(
        "k,kn->n", np.asarray(coeff, np.float64),
        np.asarray(flat, np.float64)) + np.asarray(noise, np.float64)


@pytest.mark.parametrize("k,n", [(4, 1000), (1, 257), (3, TILE_ELEMS + 3)])
def test_ota_kernel_matches_oracle(k, n):
    rng = np.random.default_rng(k * 1000 + n)
    flat = jnp.asarray(rng.standard_normal((k, n)).astype(np.float32))
    coeff = jnp.asarray(rng.dirichlet(np.ones(k)).astype(np.float32))
    noise = jnp.asarray((rng.standard_normal(n) * 1e-3).astype(np.float32))
    out = ota_aggregate_pallas(flat, coeff, noise)
    assert out.dtype == jnp.float32 and out.shape == (n,)
    np.testing.assert_allclose(
        np.asarray(out, np.float64), _oracle(flat, coeff, noise),
        rtol=1e-5, atol=1e-6)


def test_ota_kernel_k0_degenerates_to_noise_floor():
    rng = np.random.default_rng(0)
    noise = jnp.asarray(rng.standard_normal(500).astype(np.float32))
    out = ota_aggregate_pallas(jnp.zeros((0, 500), jnp.float32),
                               jnp.zeros((0,), jnp.float32), noise)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(noise))


def test_ota_kernel_chunked_matches_unchunked():
    """Small chunk_elems forces the lax.map slab path (with the noise strip
    chunked alongside); chunk boundaries must not touch the math."""
    rng = np.random.default_rng(42)
    n = 2 * TILE_ELEMS + 777
    flat = jnp.asarray(rng.standard_normal((3, n)).astype(np.float32))
    coeff = jnp.asarray(rng.dirichlet(np.ones(3)).astype(np.float32))
    noise = jnp.asarray((rng.standard_normal(n) * 1e-3).astype(np.float32))
    whole = ota_aggregate_pallas(flat, coeff, noise)
    chunked = ota_aggregate_pallas(flat, coeff, noise,
                                   chunk_elems=TILE_ELEMS)
    np.testing.assert_array_equal(np.asarray(chunked), np.asarray(whole))
    np.testing.assert_allclose(
        np.asarray(chunked, np.float64), _oracle(flat, coeff, noise),
        rtol=1e-5, atol=1e-6)


def test_ota_kernel_trailing_shape_roundtrip():
    rng = np.random.default_rng(5)
    deltas = jnp.asarray(rng.standard_normal((2, 6, 9)).astype(np.float32))
    coeff = jnp.asarray([0.4, 0.6], jnp.float32)
    noise = jnp.asarray(np.zeros(54, np.float32))
    out = ota_aggregate_pallas(deltas, coeff, noise)
    assert out.shape == (6, 9)
    np.testing.assert_allclose(
        np.asarray(out, np.float64),
        _oracle(deltas.reshape(2, 54), coeff, noise).reshape(6, 9),
        rtol=1e-5, atol=1e-6)


# --------------------------------------------------------------------------
# Whole-run driver equality
# --------------------------------------------------------------------------

def _assert_same_schedule_and_rates(a, b):
    assert [l.devices for l in a.logs] == [l.devices for l in b.logs]
    for la, lb in zip(a.logs, b.logs):
        np.testing.assert_array_equal(la.bits, lb.bits)
        np.testing.assert_array_equal(la.rates, lb.rates)
    np.testing.assert_array_equal(a.times(), b.times())


def test_scan_equals_per_round_bit_identical(world):
    ds, cell, shards = world
    cfg = _cfg(ota_noise=1e-9, horizon="scan")
    scanned = fl.run_horizon_scanned(ds, shards, cell, cfg)
    per_round = _run(world, dataclasses.replace(cfg, horizon="per-round"))
    _assert_same_schedule_and_rates(scanned, per_round)
    np.testing.assert_array_equal(scanned.accuracies(),
                                  per_round.accuracies())
    for x, y in zip(jax.tree_util.tree_leaves(scanned.final_params),
                    jax.tree_util.tree_leaves(per_round.final_params)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_legacy_oracle_agrees_with_batched_engine(world):
    cfg_b = _cfg(ota_noise=1e-9)
    cfg_l = dataclasses.replace(cfg_b, fl_engine="legacy")
    rb = _run(world, cfg_b)
    rl = _run(world, cfg_l)
    _assert_same_schedule_and_rates(rb, rl)
    np.testing.assert_allclose(rb.accuracies(), rl.accuracies(), atol=0.051)
    for x, y in zip(jax.tree_util.tree_leaves(rb.final_params),
                    jax.tree_util.tree_leaves(rl.final_params)):
        d = np.abs(np.asarray(x, np.float64) - np.asarray(y, np.float64))
        assert d.mean() < 1e-6 and d.max() < 2e-2


def test_noiseless_ota_run_matches_digital_uncompressed(world):
    """noise_std=0, threshold=0: the analog sum IS the weighted aggregate,
    so the whole run must track the digital uncompressed NOMA run — same
    schedule (both precomputed from the same channel draws), near-identical
    params (the OTA receiver renormalizes by the f32 weight sum)."""
    ro = _run(world, _cfg(ota_noise=0.0))
    rn = _run(world, _cfg(uplink="noma"))
    assert [l.devices for l in ro.logs] == [l.devices for l in rn.logs]
    np.testing.assert_allclose(ro.accuracies(), rn.accuracies(), atol=0.051)
    for x, y in zip(jax.tree_util.tree_leaves(ro.final_params),
                    jax.tree_util.tree_leaves(rn.final_params)):
        d = np.abs(np.asarray(x, np.float64) - np.asarray(y, np.float64))
        assert d.mean() < 1e-6 and d.max() < 2e-2


def test_ota_round_charges_one_shared_slot(world):
    """OTA airtime accounting mirrors NOMA's: one shared uplink slot per
    round regardless of group size (TDMA charges one slot per device)."""
    cell = world[1]
    ro = _run(world, _cfg(ota_noise=1e-9))
    rt = _run(world, _cfg(uplink="tdma"))
    # same gains/scheduler/powers -> same schedule; only the airtime differs
    assert [l.devices for l in ro.logs] == [l.devices for l in rt.logs]
    dt_o = np.diff(np.concatenate([[0.0], ro.times()]))
    dt_t = np.diff(np.concatenate([[0.0], rt.times()]))
    # same downlink cost both runs; uplink slot_seconds vs K*slot_seconds
    np.testing.assert_allclose(
        dt_t - dt_o,
        [(len(l.devices) - 1) * cell.slot_seconds for l in rt.logs],
        rtol=1e-6)


def test_vmapped_sweep_row_equals_scanned_run(world):
    cfg = _cfg(ota_noise=1e-9, horizon="scan")
    ds, cell, shards = world
    sweep = fl.run_horizon_vmapped(ds, shards, cell, cfg, seeds=[3, 4])
    solo = fl.run_horizon_scanned(ds, shards, cell, cfg)
    np.testing.assert_array_equal(sweep[0].accuracies(), solo.accuracies())
    assert not np.array_equal(sweep[1].accuracies(), solo.accuracies())


# --------------------------------------------------------------------------
# Config validation: pinned messages
# --------------------------------------------------------------------------

@pytest.mark.parametrize("kw,frag", [
    (dict(uplink="carrier-pigeon"), "unknown uplink"),
    (dict(uplink="ota", compression="adaptive"),
     "requires compression='none'"),
    # topk needs compression='adaptive' + batched to get past FLConfig's own
    # coherence checks and reach the check_uplink pinned message
    (dict(uplink="ota", compression="adaptive", topk=0.5,
          fl_engine="batched"), "cannot apply top-k sparsification"),
    (dict(uplink="ota", compression="none", power_mode="mapel"),
     "cannot use power_mode='mapel'"),
    (dict(uplink="noma", power_mode="ota-align"),
     "requires uplink='ota'"),
    (dict(uplink="ota", compression="none", power_mode="max",
          ota_noise=-1.0), "ota_noise must be >= 0"),
    (dict(uplink="ota", compression="none", power_mode="max",
          ota_threshold=1.0), "ota_threshold must be in"),
])
def test_flconfig_rejects_incoherent_ota_combos(kw, frag):
    base = dict(num_devices=M, group_size=3, num_rounds=3)
    with pytest.raises(ValueError, match=frag):
        FLConfig(**base, **kw)


def test_drivers_validate_call_site_uplink_override(world):
    """cfg may be coherent while the uplink= call argument is not — the
    drivers re-run check_uplink on the resolved value."""
    cfg = FLConfig(num_devices=M, group_size=3, num_rounds=2,
                   compression="adaptive", power_mode="max")
    with pytest.raises(ValueError, match="requires compression='none'"):
        _run(world, cfg, uplink="ota")


# --------------------------------------------------------------------------
# matching-pursuit policy + ota-align powers
# --------------------------------------------------------------------------

def test_matching_pursuit_registered_and_online():
    assert "matching-pursuit" in scheduling.available_policies()
    pol = scheduling.get_policy("matching-pursuit")
    assert pol.online and not pol.respects_c1 and pol.needs_norms


def test_matching_pursuit_noiseless_is_topk_by_weighted_energy():
    """lambda = 0 (ota_noise = 0) kills the channel penalty: round 0 (all
    norm estimates equal) must admit the K largest FedAvg weights."""
    rng = np.random.default_rng(1)
    gains = np.abs(rng.normal(1e-6, 5e-7, (1, 6))) + 1e-8
    w = np.asarray([0.05, 0.3, 0.1, 0.25, 0.2, 0.1])
    pol = scheduling.get_policy("matching-pursuit")
    cfg = scheduling.PolicyConfig(group_size=3, pmax=PMAX, ota_noise=0.0)
    state = pol.init_state(gains, w, cfg)
    group, _ = pol.select_round(0, state, scheduling.Observation.initial(6))
    assert set(group) == {1, 3, 4}


def test_matching_pursuit_penalizes_weak_channels():
    """With receiver noise, a heavy device behind a dead channel must lose
    to lighter devices with clean channels (the channel-inversion noise
    referral 1/h^2 outweighs its energy contribution)."""
    gains = np.asarray([[1e-9, 1e-6, 1e-6, 1e-6]])
    w = np.asarray([0.4, 0.2, 0.2, 0.2])
    pol = scheduling.get_policy("matching-pursuit")
    cfg = scheduling.PolicyConfig(group_size=2, pmax=PMAX, ota_noise=1e-8)
    state = pol.init_state(gains, w, cfg)
    group, _ = pol.select_round(0, state, scheduling.Observation.initial(4))
    assert 0 not in group and len(group) == 2


def test_matching_pursuit_live_ota_run(world):
    cfg = _cfg(scheduler="matching-pursuit", ota_noise=1e-9)
    res = _run(world, cfg)
    assert all(0 < len(l.devices) <= 3 for l in res.logs)
    assert len(res.accuracies()) == 3


def test_ota_align_powers_properties():
    gains = np.asarray([1e-6, 2e-6, 5e-7, 0.0])
    w = np.asarray([0.3, 0.2, 0.4, 0.1])
    p = power.ota_align_powers(gains, w, PMAX)
    live = slice(0, 3)
    # the binding device transmits at exactly pmax...
    assert p.max() == pytest.approx(PMAX)
    assert np.all(p <= PMAX * (1 + 1e-12))
    # ...alignment: p_k h_k^2 / w_k^2 = eta constant across live devices
    eta = p[live] * gains[live] ** 2 / w[live] ** 2
    np.testing.assert_allclose(eta, eta[0], rtol=1e-9)
    # dead channel transmits nothing
    assert p[3] == 0.0
    # allocator front door
    alloc = power.make_power_allocator("ota-align", PMAX, 1e-13)
    np.testing.assert_array_equal(alloc(gains, w), p)
    batched = alloc.batched(np.stack([gains, gains]), np.stack([w, w]))
    np.testing.assert_array_equal(batched[0], p)
    np.testing.assert_array_equal(batched[1], p)
