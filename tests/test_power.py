"""MAPEL power allocation (paper §III-C) vs grid oracle + structure tests."""
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # offline container: seeded numpy-backed shim
    from _propcheck import given, settings, strategies as st

from repro.core import power

NOISE = 1.6e-14
PMAX = 0.01


def _instance(k, seed):
    rng = np.random.default_rng(seed)
    gains = np.abs(rng.normal(1e-6, 5e-7, k)) + 1e-8
    w = rng.dirichlet(np.ones(k))
    return gains, w


def test_min_powers_closed_form_inverts_targets():
    """Eq. (13): minimal powers reproduce the requested z targets exactly."""
    gains = np.sort(_instance(3, 0)[0])[::-1]
    z = np.array([1.5, 2.0, 3.0])
    p = power.min_powers_for_targets(z, gains, NOISE)
    # recompute z from p
    for k in range(3):
        mu = np.sum(p[k:] * gains[k:] ** 2) + NOISE
        phi = np.sum(p[k + 1 :] * gains[k + 1 :] ** 2) + NOISE
        assert mu / phi == pytest.approx(z[k], rel=1e-9)


@settings(max_examples=20, deadline=None)
@given(st.integers(2, 3), st.integers(0, 10_000))
def test_mapel_beats_or_matches_grid(k, seed):
    gains, w = _instance(k, seed)
    sol = power.mapel(gains, w, PMAX, NOISE, eps=1e-4)
    grid = power.grid_oracle(gains, w, PMAX, NOISE, points=15)
    # MAPEL should be within the grid's resolution of the optimum (and is
    # usually above the coarse grid value).
    assert sol.weighted_rate >= grid.weighted_rate * (1 - 2e-2)
    assert np.all(sol.powers <= PMAX * (1 + 1e-9))
    assert np.all(sol.powers >= -1e-12)


def test_mapel_single_user_max_power():
    gains, w = _instance(1, 3)
    sol = power.mapel(gains, np.ones(1), PMAX, NOISE)
    assert sol.powers[0] == pytest.approx(PMAX)


def test_weighted_rate_matches_noma_module():
    import jax.numpy as jnp

    from repro.core import noma

    gains, w = _instance(3, 5)
    p = np.random.default_rng(5).uniform(0, PMAX, 3)
    ours = power.weighted_rate(p, gains, w, NOISE)
    ref = float(
        noma.weighted_sum_rate(jnp.asarray(p), jnp.asarray(gains), jnp.asarray(w), NOISE)
    )
    assert ours == pytest.approx(ref, rel=1e-5)


@settings(max_examples=8, deadline=None)
@given(st.integers(1, 4), st.integers(0, 9999))
def test_mapel_batched_matches_sequential(k, seed):
    """The lockstep polyblock (schedulers' finalization path) is mapel()
    group-for-group — bit-identical powers, rates, iteration counts, gaps."""
    rng = np.random.default_rng(seed)
    groups = 5
    gains = np.abs(rng.normal(1e-6, 5e-7, (groups, k))) + 1e-8
    w = rng.dirichlet(np.ones(k), size=groups)
    batched = power.mapel_batched(gains, w, PMAX, NOISE, eps=1e-3)
    for i in range(groups):
        seq = power.mapel(gains[i], w[i], PMAX, NOISE, eps=1e-3)
        np.testing.assert_array_equal(batched.powers[i], seq.powers)
        assert batched.weighted_rates[i] == seq.weighted_rate
        assert batched.iterations[i] == seq.iterations
        assert batched.gaps[i] == seq.gap


def test_mapel_batched_empty():
    out = power.mapel_batched(np.zeros((0, 3)), np.zeros((0, 3)), PMAX, NOISE)
    assert out.powers.shape == (0, 3)
    assert out.weighted_rates.shape == (0,)


def test_mapel_batched_k1_closed_form_matches_sequential():
    """K=1 takes the closed-form branch in BOTH drivers: full power, the
    interference-free rate, zero iterations, zero gap — and the batched
    rows must equal the sequential solves bit for bit (same formula, no
    polyblock float drift to hide behind)."""
    rng = np.random.default_rng(11)
    gains = np.abs(rng.normal(1e-6, 5e-7, (4, 1))) + 1e-8
    w = rng.dirichlet(np.ones(1), size=4)
    batched = power.mapel_batched(gains, w, PMAX, NOISE, eps=1e-3)
    np.testing.assert_array_equal(batched.powers, np.full((4, 1), PMAX))
    np.testing.assert_array_equal(batched.iterations, np.zeros(4, dtype=int))
    np.testing.assert_array_equal(batched.gaps, np.zeros(4))
    for i in range(4):
        seq = power.mapel(gains[i], w[i], PMAX, NOISE, eps=1e-3)
        np.testing.assert_array_equal(batched.powers[i], seq.powers)
        assert batched.weighted_rates[i] == seq.weighted_rate


def test_mapel_batched_near_zero_gains_matches_sequential():
    """Gains at the numerical floor (deep-fade devices, ~1e-12 amplitude):
    the z targets collapse to ~1 and log2 terms to ~0, the regime where the
    projection bisections and back-substitutions are most cancellation-
    prone.  The lockstep driver must still walk the identical float path as
    the sequential solver — bit-equal powers, rates, iterations, gaps —
    including rows that MIX a healthy gain with near-dead ones."""
    rng = np.random.default_rng(13)
    gains = np.abs(rng.normal(1e-12, 5e-13, (5, 3))) + 1e-15
    gains[2, 0] = 1e-6            # one healthy device among the dead
    gains[4] = 1e-15              # a whole row at the floor
    w = rng.dirichlet(np.ones(3), size=5)
    batched = power.mapel_batched(gains, w, PMAX, NOISE, eps=1e-3)
    assert np.all(np.isfinite(batched.powers))
    assert np.all(batched.powers >= -1e-12)
    assert np.all(batched.powers <= PMAX * (1 + 1e-9))
    for i in range(5):
        seq = power.mapel(gains[i], w[i], PMAX, NOISE, eps=1e-3)
        np.testing.assert_array_equal(batched.powers[i], seq.powers)
        assert batched.weighted_rates[i] == seq.weighted_rate
        assert batched.iterations[i] == seq.iterations
        assert batched.gaps[i] == seq.gap


def _paper_cell_groups(k, seed=0, groups=35):
    """Groups of the paper's cell (CellConfig: 10-500 m, path-loss exponent
    3, Rayleigh fading): one random K-device group per round, weighted by
    their shard fractions."""
    import jax

    from repro.core import channel

    cell = channel.CellConfig()
    key = jax.random.PRNGKey(seed)
    dist = channel.sample_positions(jax.random.fold_in(key, 1), cell)
    gains_tm = np.asarray(channel.sample_round_channels(
        jax.random.fold_in(key, 2), dist, cell, groups), dtype=np.float64)
    rng = np.random.default_rng(seed)
    sizes = rng.lognormal(0.0, 0.4, cell.num_devices)
    devs = np.stack([rng.choice(cell.num_devices, k, replace=False)
                     for _ in range(groups)])
    return (gains_tm[np.arange(groups)[:, None], devs],
            (sizes / sizes.sum())[devs], cell)


@pytest.mark.parametrize("k,max_iter", [(3, 300), (4, 60), (2, 3), (3, 20)])
def test_mapel_batched_matches_sequential_at_paper_cell_shape(k, max_iter):
    """35 groups of the paper's cell: vertex lists grow to hundreds (the
    store's capacity doubles), some groups converge and some stop at
    ``max_iter``, and every row is still mapel() bit for bit."""
    gains, w, cell = _paper_cell_groups(k)
    pmax, noise = cell.max_power_w, cell.noise_power_w
    batched = power.mapel_batched(gains, w, pmax, noise, max_iter=max_iter)
    stopped = (batched.iterations >= max_iter) & (batched.gaps > 1e-3)
    assert stopped.any() and not stopped.all()
    for i in range(len(gains)):
        seq = power.mapel(gains[i], w[i], pmax, noise, max_iter=max_iter)
        np.testing.assert_array_equal(batched.powers[i], seq.powers)
        assert batched.weighted_rates[i] == seq.weighted_rate
        assert batched.iterations[i] == seq.iterations
        assert batched.gaps[i] == seq.gap


def _projection_rows(case):
    """(z, gains sorted strongest first) rows for one projection edge case."""
    rng = np.random.default_rng(17)
    g = np.sort(np.abs(rng.normal(1e-6, 5e-7, (4, 3))) + 1e-8, axis=1)[:, ::-1]
    z_top = 1.0 + PMAX * g * g / NOISE
    if case == "z_one":
        return np.ones((1, 3)), g[:1]
    if case == "unit_coordinate":
        z = 1.0 + np.array([[0.0, 0.7, 0.4], [0.9, 0.3, 0.0]]) * (z_top[:2] - 1.0)
        return z, g[:2]
    if case == "near_zero_gains":
        g0 = np.abs(np.random.default_rng(13).normal(1e-12, 5e-13, (5, 3))) + 1e-15
        g0[2, 0] = 1e-6
        g0[4] = 1e-15
        g0 = np.sort(g0, axis=1)[:, ::-1]
        return 1.0 + PMAX * g0 * g0 / NOISE, g0
    if case == "infeasible_first_mid":
        return 1.0 + 3.0 * (z_top - 1.0), g
    if case == "below_one":
        z = z_top.copy()
        z[:2, 1] = 1.0 - 1e-9
        return z, g
    parts = [_projection_rows(c) for c in _PROJECTION_CASES[:-1]]
    return (np.concatenate([z for z, _ in parts]),
            np.concatenate([g for _, g in parts]))


_PROJECTION_CASES = ("z_one", "unit_coordinate", "near_zero_gains",
                     "infeasible_first_mid", "below_one", "mix")


@pytest.mark.parametrize("tol", [1e-12, 1e-10])
@pytest.mark.parametrize("case", _PROJECTION_CASES)
def test_project_batched_matches_one_level_bisection(case, tol):
    """The multi-level projection walks the one-level bisection's path: each
    row equals _project's bit for bit, on rows where every mid is feasible,
    none is, the first is not, the gains sit at the numerical floor, and a
    target lies below 1; tol=1e-10 ends on a partial pass (34 levels)."""
    z, g = _projection_rows(case)
    got = power._project_batched(z, g, PMAX, NOISE, tol=tol)
    for i in range(len(z)):
        np.testing.assert_array_equal(
            got[i], power._project(z[i], g[i], PMAX, NOISE, tol=tol))


def test_mapel_gap_reported():
    gains, w = _instance(3, 7)
    sol = power.mapel(gains, w, PMAX, NOISE, eps=1e-3, max_iter=300)
    # either converged to the certificate gap or hit the vertex cap
    assert (0 <= sol.gap <= 1e-3) or sol.iterations >= 300


# --------------------------------------------------------------------------
# PowerAllocator: the promoted make_power_fn (solve / solve_batched)
# --------------------------------------------------------------------------

def test_power_allocator_mapel_matches_scalar_and_batched():
    alloc = power.make_power_allocator("mapel", PMAX, NOISE)
    g1, w1 = _instance(3, 21)
    g2, w2 = _instance(3, 22)
    np.testing.assert_array_equal(
        alloc.solve(g1, w1), power.mapel(g1, w1, PMAX, NOISE, eps=1e-3).powers
    )
    g_vk = np.stack([g1, g2])
    w_vk = np.stack([w1, w2])
    np.testing.assert_array_equal(
        alloc.solve_batched(g_vk, w_vk),
        power.mapel_batched(g_vk, w_vk, PMAX, NOISE, eps=1e-3).powers,
    )
    # batched rows == per-group scalar solves (the lockstep guarantee,
    # reachable through the allocator API)
    np.testing.assert_array_equal(alloc.solve_batched(g_vk, w_vk)[0],
                                  alloc.solve(g1, w1))


def test_power_allocator_max_mode_and_powerfn_compat():
    """The allocator must drop into legacy PowerFn call sites: callable and
    carrying a ``batched`` attribute."""
    alloc = power.make_power_allocator("max", PMAX, NOISE)
    g, w = _instance(3, 23)
    np.testing.assert_array_equal(alloc(g, w), np.full(3, PMAX))
    np.testing.assert_array_equal(
        alloc.batched(np.stack([g, g]), np.stack([w, w])),
        np.full((2, 3), PMAX),
    )
    assert alloc(g, w) is not None and callable(alloc.batched)


def test_power_allocator_unknown_mode_raises():
    with pytest.raises(ValueError, match="power mode"):
        power.make_power_allocator("psycho", PMAX, NOISE)
