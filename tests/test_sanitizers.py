"""Recompile & NaN sanitizer regression tests (tools/flcheck/sanitizers).

The compile-count guard pins the repo's central trace-safety invariant:
every FL driver compiles a *constant* number of XLA programs no matter
how the horizon scales — ``run_federated_learning`` (scan) across round
counts, ``run_horizon_vmapped`` across seed counts, and the per-round
batched engine across round counts.  A per-round or per-seed retrace
(the PR 7 ``jax.jit(bound_method)`` class of bug) shows up as a count
that grows with the sweep, which these tests turn into a hard failure.

Counting protocol: XLA backend-compile counts are process-wide, so each
test warms up first — one run at a *different* horizon size (caches every
shape-independent program), plus the per-size ``jax.random.split`` setup
programs (an O(1)-per-size cost that would otherwise alias: ``split(key,
2)`` shares its program with the ubiquitous 2-way ``split(key)``).  The
counted runs then compile exactly the size-specific driver programs,
whose number must match.

Because the cache is process-wide, every *counted* size here must be
unique across the whole tier-1 suite: a different test file running the
same horizon length caches that size's small ``(T,)``-shaped programs and
skews one side of the comparison (T=2 once measured 1 vs 17 for T=8 in a
full-suite run — 14 other call sites use ``num_rounds=2``).  Counted
sizes: rounds 6/11 (scan), 5/9 (per-round), 7/12 (online scan,
tests/test_policy_scan.py), seed-sweep widths 1/4 — keep them unused
elsewhere.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import FLConfig
from repro.core import channel, fl
from repro.data import dirichlet_partition, make_mnist_like
from tools.flcheck.sanitizers import compile_count, nan_guard

M = 6


@pytest.fixture(scope="module")
def world():
    ds = make_mnist_like(num_samples=300, seed=0)
    cell = channel.CellConfig(num_devices=M)
    shards = dirichlet_partition(ds.y_train, M, seed=0)
    return ds, cell, shards


def _cfg(rounds, *, horizon="scan", seed=0):
    return FLConfig(num_devices=M, group_size=2, num_rounds=rounds,
                    scheduler="lazy-gwmin", power_mode="max",
                    compression="adaptive", fl_engine="batched",
                    horizon=horizon, seed=seed)


def _warm_key_splits(*sizes):
    key = jax.random.PRNGKey(0)
    for n in sizes:
        jax.random.split(key, n)


# --------------------------------------------------------------------------
# driver compile counts: constant across horizon scaling
# --------------------------------------------------------------------------

def test_scan_compile_count_constant_in_rounds(world):
    ds, cell, shards = world
    fl.run_federated_learning(ds, shards, cell, _cfg(3))   # warm T=3
    _warm_key_splits(6, 11)
    counts = {}
    for t_rounds in (6, 11):
        with compile_count() as tally:
            fl.run_federated_learning(ds, shards, cell, _cfg(t_rounds))
        counts[t_rounds] = tally.count
    assert counts[6] == counts[11], (
        f"scan driver compile count scales with rounds: {counts}"
    )
    assert counts[6] > 0   # each T is a fresh static shape: must compile

    with compile_count() as tally:
        fl.run_federated_learning(ds, shards, cell, _cfg(6))
    assert tally.count == 0, "identical rerun must be fully cached"


def test_vmapped_compile_count_constant_in_seeds(world):
    ds, cell, shards = world
    cfg = _cfg(2)
    fl.run_horizon_vmapped(ds, shards, cell, cfg, seeds=range(2))  # warm S=2
    counts = {}
    for s in (1, 4):
        with compile_count() as tally:
            fl.run_horizon_vmapped(ds, shards, cell, cfg, seeds=range(s))
        counts[s] = tally.count
    assert counts[1] == counts[4], (
        f"vmapped driver compile count scales with seeds: {counts}"
    )
    assert counts[4] > 0

    with compile_count() as tally:
        fl.run_horizon_vmapped(ds, shards, cell, cfg, seeds=range(4))
    assert tally.count == 0, "identical rerun must be fully cached"


@pytest.fixture(scope="module")
def equal_world():
    """Equal-size shards: the batched engine jits ``_round_step`` with the
    group's batch count ``nb`` static, so under non-iid Dirichlet shards
    the program count tracks which nb values the *schedule* happens to
    draw — content, not round count.  Equal shards collapse nb to one
    static value, isolating the invariant this test pins (no per-round
    retrace)."""
    ds = make_mnist_like(num_samples=300, seed=0)
    cell = channel.CellConfig(num_devices=M)
    per = len(ds.y_train) // M
    shards = [np.arange(i * per, (i + 1) * per) for i in range(M)]
    return ds, cell, shards


def test_batched_engine_compile_count_constant_in_rounds(equal_world):
    ds, cell, shards = equal_world
    fl.run_federated_learning(ds, shards, cell,
                              _cfg(3, horizon="per-round"))   # warm T=3
    _warm_key_splits(5, 9)
    counts = {}
    for t_rounds in (5, 9):
        with compile_count() as tally:
            fl.run_federated_learning(ds, shards, cell,
                                      _cfg(t_rounds, horizon="per-round"))
        counts[t_rounds] = tally.count
    assert counts[5] == counts[9], (
        f"per-round batched engine compile count scales with rounds: {counts}"
    )


# --------------------------------------------------------------------------
# the PR 7 bound-method recompile, pinned as a live repro (FLC001's bug)
# --------------------------------------------------------------------------

class _Model:
    def accuracy(self, params, x):
        return jnp.mean(params * x)


def _accuracy(params, x):
    return jnp.mean(params * x)


_jit_accuracy = jax.jit(_accuracy)   # the fix: module-level, stable identity


def test_bound_method_jit_recompiles_per_call():
    m = _Model()
    p, x = jnp.ones(16), jnp.ones(16)
    jax.jit(m.accuracy)(p, x).block_until_ready()  # flcheck: disable=FLC001
    with compile_count() as bad:
        for _ in range(3):
            fn = jax.jit(m.accuracy)   # flcheck: disable=FLC001
            fn(p, x).block_until_ready()
    # each call wraps a fresh bound-method object: the jit cache misses
    # every time (this is the 2.2x PR 7 slowdown, kept as a live repro)
    assert bad.count >= 3, f"expected a compile per call, got {bad.count}"

    _jit_accuracy(p, x).block_until_ready()
    with compile_count() as good:
        for _ in range(3):
            _jit_accuracy(p, x).block_until_ready()
    assert good.count == 0, "module-level jit must hit its cache"


# --------------------------------------------------------------------------
# NaN sanitizer
# --------------------------------------------------------------------------

def test_nan_guard_raises_at_source_and_restores():
    prev = jax.config.jax_debug_nans
    with pytest.raises(FloatingPointError):
        with nan_guard():
            jnp.divide(jnp.zeros(()), jnp.zeros(())).block_until_ready()
    assert jax.config.jax_debug_nans == prev


def test_nan_guard_restores_on_clean_exit():
    prev = jax.config.jax_debug_nans
    with nan_guard():
        assert jax.config.jax_debug_nans is True
        jnp.ones(4).block_until_ready()
    assert jax.config.jax_debug_nans == prev


def test_nan_guard_disabled_passes_nans_through():
    with nan_guard(enable=False):
        out = jnp.divide(jnp.zeros(()), jnp.zeros(()))
    assert np.isnan(np.asarray(out))


# --------------------------------------------------------------------------
# an instance's draws: one program per (model, M, T), whatever the seed/cell
# --------------------------------------------------------------------------

def test_draw_program_compiles_once_across_seeds_and_cells():
    """The seeds are integer operands and no cell scalar enters the draw
    program, so seeds (above 2**31 too) and cells of equal M reuse it; the
    eager arithmetic after it takes the cell's scalars as operands too."""
    from repro.models.fl_models import get_fl_model

    model = get_fl_model("lenet")
    cells = (
        channel.CellConfig(num_devices=M),
        channel.CellConfig(num_devices=M, cell_radius_m=250.0,
                           carrier_hz=5.8e9, antenna_gain=2.0,
                           path_loss_exp=3.5),
    )
    fl._instance_draws(1, cells[0], model, 4)   # warm (model, M, T=4)
    with compile_count() as tally:
        for cell in cells:
            for seed in (2, 9100011, 2300000011, 3100000024):
                fl._instance_draws(seed, cell, model, 4)
    assert tally.count == 0, f"draws recompiled {tally.count} programs"
    with compile_count() as tally:
        fl._instance_draws(2, cells[0], model, 13)   # a new T: a new shape
    assert tally.count > 0
