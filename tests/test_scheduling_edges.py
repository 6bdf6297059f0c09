"""Scheduler edge cases (T*K > M horizons) and the jax backend equivalence.

Regression coverage for the crash/bias sweep: every scheduler must survive
horizons that exhaust the device set (Yang et al. 2019 comparison regime),
emitting empty tail groups instead of crashing; and the device-resident
greedy (``backend="jax"``) must reproduce the numpy path bit-for-bit.
"""
import numpy as np
import pytest

from repro.core import scheduling

NOISE = 1.6e-14


def _instance(m, t, seed):
    rng = np.random.default_rng(seed)
    gains = np.abs(rng.normal(1e-6, 5e-7, (t, m))) + 1e-8
    w = rng.dirichlet(np.ones(m))
    return gains, w


def _make(name, gains, w, k):
    if name == "lazy-gwmin":
        return scheduling.lazy_greedy_schedule(gains, w, k, noise_power=NOISE)
    if name == "literal-gwmin":
        return scheduling.literal_graph_schedule(gains, w, k, noise_power=NOISE)
    if name == "random":
        rng = np.random.default_rng(0)
        return scheduling.random_schedule(rng, gains, w, k, noise_power=NOISE)
    if name == "round-robin":
        return scheduling.round_robin_schedule(gains, w, k, noise_power=NOISE)
    if name == "proportional-fair":
        return scheduling.proportional_fair_schedule(gains, w, k, noise_power=NOISE)
    raise ValueError(name)


# --------------------------------------------------------------------------
# T*K > M: the horizon exhausts the device set
# --------------------------------------------------------------------------

@pytest.mark.parametrize(
    "name",
    ["lazy-gwmin", "literal-gwmin", "random", "round-robin", "proportional-fair"],
)
@pytest.mark.parametrize("m,t,k", [(5, 4, 2), (4, 3, 2), (6, 8, 1)])
def test_tk_exceeds_m_no_crash(name, m, t, k):
    """All five schedulers must survive T*K > M: C1/C2 hold, every id is in
    range, and rounds beyond the device supply come back empty, not bogus."""
    gains, w = _instance(m, t, seed=3)
    sched = _make(name, gains, w, k)
    assert sched.validate(m, k)
    assert len(sched.rounds) == t
    assert all(len(grp) <= k for grp in sched.rounds)
    # no device can appear anywhere once all M are used
    assert sum(len(grp) for grp in sched.rounds) <= m
    assert len(sched.scheduled_devices()) == sum(len(g) for g in sched.rounds)


@pytest.mark.parametrize("name", ["round-robin", "proportional-fair"])
def test_exhausting_schedulers_cover_all_devices_then_go_empty(name):
    """The sequential policies schedule every device and then emit () tails
    (proportional-fair used to crash here: an empty ``avail`` built with
    ``np.array([])`` is float64 and rejects fancy indexing)."""
    m, t, k = 4, 3, 2
    gains, w = _instance(m, t, seed=7)
    sched = _make(name, gains, w, k)
    assert sched.scheduled_devices() == set(range(m))
    assert sched.rounds[-1] == ()


def test_proportional_fair_empty_avail_regression():
    """Direct regression for src/repro/core/scheduling.py PF indexing: with
    T*K well past M the scheduler iterates many all-empty rounds."""
    gains, w = _instance(3, 6, seed=0)
    sched = scheduling.proportional_fair_schedule(gains, w, 2, noise_power=NOISE)
    assert sched.validate(3, 2)
    assert sched.rounds[2:] == [(), (), (), ()]


# --------------------------------------------------------------------------
# device backends: fused while_loop and step-wise greedy == numpy, bit for bit
# --------------------------------------------------------------------------

EDGE_GRID = [
    (8, 2, 3, 24, 0),      # pool >= M: full enumeration
    (12, 3, 3, 24, 1),
    (32, 3, 4, 24, 2),     # proxy-ranked pool (M > pool)
    (24, 3, 4, 8, 3),
    (32, 2, 5, 8, 4),
    (5, 2, 4, 24, 5),      # T*K > M: host tail path for leftover groups
    (30, 3, 11, 8, 6),     # T*K > M with proxy pool
    (10, 3, 3, 2, 7),      # pool < K: groups shrink to the pool size
]


@pytest.mark.parametrize("backend", ["jax", "jax-stepwise"])
@pytest.mark.parametrize("m,k,t,pool,seed", EDGE_GRID)
def test_jax_backend_bit_identical(m, k, t, pool, seed, backend):
    pytest.importorskip("jax")
    gains, w = _instance(m, t, seed)
    a = scheduling.lazy_greedy_schedule(
        gains, w, k, noise_power=NOISE, candidate_pool=pool
    )
    b = scheduling.lazy_greedy_schedule(
        gains, w, k, noise_power=NOISE, candidate_pool=pool, backend=backend
    )
    assert a.rounds == b.rounds
    for pa, pb in zip(a.powers, b.powers):
        np.testing.assert_array_equal(pa, pb)
    for ra, rb in zip(a.rates, b.rates):
        np.testing.assert_array_equal(ra, rb)
    assert a.weighted_sum_rate == b.weighted_sum_rate
    assert b.validate(m, k)


@pytest.mark.parametrize("m,k,t,pool,seed", EDGE_GRID)
def test_fused_equals_stepwise_selection(m, k, t, pool, seed):
    """The fused while_loop driver must walk the exact vertex sequence the
    step-wise driver walks: identical rounds straight out of selection."""
    pytest.importorskip("jax")
    gains, w = _instance(m, t, seed)
    fused = scheduling._lazy_gwmin_rounds(
        gains, w, k, pmax=0.01, noise_power=NOISE, candidate_pool=pool,
        backend="jax",
    )
    stepwise = scheduling._lazy_gwmin_rounds(
        gains, w, k, pmax=0.01, noise_power=NOISE, candidate_pool=pool,
        backend="jax-stepwise",
    )
    assert fused == stepwise


@pytest.mark.parametrize("backend", ["jax", "jax-stepwise"])
def test_jax_backend_bit_identical_with_mapel_refinement(backend):
    """Selection equality carries through the batched MAPEL finalization."""
    pytest.importorskip("jax")
    gains, w = _instance(10, 3, seed=11)
    a = scheduling.lazy_greedy_schedule(
        gains, w, 2, power_mode="mapel", noise_power=NOISE
    )
    b = scheduling.lazy_greedy_schedule(
        gains, w, 2, power_mode="mapel", noise_power=NOISE, backend=backend
    )
    assert a.rounds == b.rounds
    for pa, pb in zip(a.powers, b.powers):
        np.testing.assert_array_equal(pa, pb)
    assert a.weighted_sum_rate == b.weighted_sum_rate


def test_unknown_backend_raises():
    gains, w = _instance(6, 2, seed=0)
    with pytest.raises(ValueError, match="backend"):
        scheduling.lazy_greedy_schedule(
            gains, w, 2, noise_power=NOISE, backend="tpu-v9"
        )


# --------------------------------------------------------------------------
# fused-backend switches: pallas scorer and vertex-axis sharding
# --------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,t,pool,seed", [
    (20, 3, 4, 12, 9),
    (32, 2, 5, 8, 4),
    (5, 2, 4, 24, 5),      # T*K > M tail after the fused loop
])
def test_pallas_scorer_agrees_with_xla_scorer(m, k, t, pool, seed):
    """The Pallas SIC kernel scorer accumulates in f32 (ULP-level score
    differences vs the f64 XLA comparison-matrix), but the greedy argmax is
    insensitive on non-degenerate instances: same schedules."""
    pytest.importorskip("jax")
    gains, w = _instance(m, t, seed)
    a = scheduling.lazy_greedy_schedule(
        gains, w, k, noise_power=NOISE, candidate_pool=pool, backend="jax",
        scorer="xla",
    )
    b = scheduling.lazy_greedy_schedule(
        gains, w, k, noise_power=NOISE, candidate_pool=pool, backend="jax",
        scorer="pallas",
    )
    assert a.rounds == b.rounds
    assert a.weighted_sum_rate == b.weighted_sum_rate


def test_unknown_scorer_raises():
    pytest.importorskip("jax")
    gains, w = _instance(6, 2, seed=0)
    with pytest.raises(ValueError, match="scorer"):
        scheduling.lazy_greedy_schedule(
            gains, w, 2, noise_power=NOISE, backend="jax", scorer="cuda"
        )


@pytest.mark.parametrize("shards", [1, 4])
def test_sharded_fused_loop_bit_identical(shards):
    """shard_map over the vertex axis (in-mesh argmax reduction) must not
    change the schedule.  shards=1 exercises the collective code path on a
    single-device mesh.  Multi-shard equality needs that many devices
    (``XLA_FLAGS=--xla_force_host_platform_device_count=4``); with fewer,
    the request must raise rather than run on fewer shards in silence."""
    jax = pytest.importorskip("jax")
    gains, w = _instance(24, 4, seed=12)
    a = scheduling.lazy_greedy_schedule(
        gains, w, 3, noise_power=NOISE, candidate_pool=10
    )
    if shards > jax.local_device_count():
        with pytest.raises(ValueError, match="local devices are present"):
            scheduling.lazy_greedy_schedule(
                gains, w, 3, noise_power=NOISE, candidate_pool=10,
                backend="jax", shards=shards,
            )
        return
    b = scheduling.lazy_greedy_schedule(
        gains, w, 3, noise_power=NOISE, candidate_pool=10, backend="jax",
        shards=shards,
    )
    assert a.rounds == b.rounds
    assert a.weighted_sum_rate == b.weighted_sum_rate


# --------------------------------------------------------------------------
# degenerate batch shapes: greedy_step pool > M must match the host clamp
# --------------------------------------------------------------------------

def test_greedy_step_clamps_candidate_pool_beyond_m():
    """Regression: calling the jitted ``greedy_step`` directly with
    pool > M used to be a broadcast-shape crash; the host driver clamps the
    pool to M, and the jitted path must behave identically."""
    jax = pytest.importorskip("jax")
    import itertools

    import jax.numpy as jnp

    from repro.core import rates_jax

    m, t, pool = 6, 3, 16
    gains, w = _instance(m, t, seed=0)
    solo = w * np.log2(1.0 + (0.01 * gains**2) / NOISE)
    with jax.enable_x64(True):
        jg = jnp.asarray(gains, jnp.float64)
        jw = jnp.asarray(w, jnp.float64)
        jsolo = jnp.asarray(solo, jnp.float64)
        avail = jnp.ones(m, bool)
        done = jnp.zeros(t, bool)
        subs = jnp.asarray(np.array(
            list(itertools.combinations(range(m), 2)), np.int32))
        big = rates_jax.greedy_step(
            jg, jw, jsolo, subs, avail, done,
            pool=pool, pmax=0.01, noise_power=NOISE)
        ref = rates_jax.greedy_step(
            jg, jw, jsolo, subs, avail, done,
            pool=m, pmax=0.01, noise_power=NOISE)
        assert float(big[0]) == float(ref[0])
        assert int(big[1]) == int(ref[1])
        np.testing.assert_array_equal(np.asarray(big[2]), np.asarray(ref[2]))
        # a naive caller enumerating positions over the unclamped pool gets
        # the out-of-range subsets masked infeasible, not a crash
        subs_naive = jnp.asarray(np.array(
            list(itertools.combinations(range(pool), 2)), np.int32))
        naive = rates_jax.greedy_step(
            jg, jw, jsolo, subs_naive, avail, done,
            pool=pool, pmax=0.01, noise_power=NOISE)
        assert float(naive[0]) == float(ref[0])
        np.testing.assert_array_equal(np.asarray(naive[2]), np.asarray(ref[2]))


def test_lazy_greedy_pool_beyond_m_matches_exact_pool():
    """End-to-end: candidate_pool > M is the full-cell enumeration."""
    gains, w = _instance(7, 3, seed=2)
    a = scheduling.lazy_greedy_schedule(
        gains, w, 2, noise_power=NOISE, candidate_pool=100, backend="jax"
    )
    b = scheduling.lazy_greedy_schedule(
        gains, w, 2, noise_power=NOISE, candidate_pool=7
    )
    assert a.rounds == b.rounds
