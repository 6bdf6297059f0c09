"""NOMA/SIC properties (paper Eq. 4-6), incl. hypothesis property tests."""
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # offline container: seeded numpy-backed shim
    from _propcheck import given, settings, strategies as st

from repro.core import noma

NOISE = 1e-13


def _gains(k, seed=0):
    return np.abs(np.random.default_rng(seed).normal(1e-6, 5e-7, k)) + 1e-8


def test_sic_sum_rate_identity():
    """Fundamental SIC identity: sum_k log2(1+SINR_k) == log2(1 + sum rx / sigma^2).

    Successive cancellation makes the (unweighted) sum rate equal the
    multiple-access-channel capacity, independent of decode order."""
    g = jnp.asarray(_gains(4))
    p = jnp.full(4, 0.01)
    rates = noma.rates(p, g, NOISE)
    total_rx = jnp.sum(p * g**2)
    np.testing.assert_allclose(
        float(jnp.sum(rates)), float(jnp.log2(1 + total_rx / NOISE)), rtol=1e-5
    )


@settings(max_examples=50, deadline=None)
@given(
    st.integers(2, 5),
    st.integers(0, 2**31 - 1),
)
def test_sic_sum_rate_identity_property(k, seed):
    g = jnp.asarray(_gains(k, seed))
    p = jnp.asarray(np.random.default_rng(seed + 1).uniform(1e-4, 0.01, k))
    rates = noma.rates(p, g, NOISE)
    # Reference in f64.  The f32 rates carry an absolute error of a few
    # 1e-7 bits, which at a weak group's ~4e-4-bit sum rate exceeds rtol
    # alone, so the bound is rtol plus an absolute 1e-6 bits.
    p64, g64 = np.asarray(p, np.float64), np.asarray(g, np.float64)
    np.testing.assert_allclose(
        float(jnp.sum(rates)),
        np.log2(1 + np.sum(p64 * g64**2) / NOISE),
        rtol=1e-4, atol=1e-6,
    )


def test_sinr_strongest_decoded_first():
    g = jnp.asarray(_gains(3))
    p = jnp.full(3, 0.01)
    rx = np.asarray(p * g**2)
    s = np.asarray(noma.sinr(p, g, NOISE))
    strongest = int(np.argmax(rx))
    weakest = int(np.argmin(rx))
    # strongest sees all others as interference; weakest sees none
    assert s[strongest] == pytest.approx(
        rx[strongest] / (rx.sum() - rx[strongest] + NOISE), rel=1e-5
    )
    assert s[weakest] == pytest.approx(rx[weakest] / NOISE, rel=1e-5)


def test_rates_permutation_equivariant():
    g = _gains(4)
    p = np.random.default_rng(1).uniform(1e-3, 0.01, 4)
    r = np.asarray(noma.rates(jnp.asarray(p), jnp.asarray(g), NOISE))
    perm = np.array([2, 0, 3, 1])
    r2 = np.asarray(noma.rates(jnp.asarray(p[perm]), jnp.asarray(g[perm]), NOISE))
    np.testing.assert_allclose(r[perm], r2, rtol=1e-5)


def test_tdma_rates_exceed_noma_per_user():
    """Without interference each user's rate can only go up."""
    g = jnp.asarray(_gains(3))
    p = jnp.full(3, 0.01)
    assert bool(jnp.all(noma.tdma_rates(p, g, NOISE) >= noma.rates(p, g, NOISE) - 1e-9))


def test_bit_budget_scales_with_bandwidth_and_time():
    g = jnp.asarray(_gains(2))
    p = jnp.full(2, 0.01)
    b1 = noma.bit_budget(p, g, NOISE, 4e6, 0.2)
    b2 = noma.bit_budget(p, g, NOISE, 8e6, 0.1)
    np.testing.assert_allclose(np.asarray(b1), np.asarray(b2), rtol=1e-6)
