"""Compile the main path's Pallas kernels for a TPU v5e, with no chip attached.

The TPU compiler is installed beside the CPU backend, so a described
``v5e:2x2`` topology lets Mosaic lower and compile each kernel here at the
FL horizon's real widths (LeNet-300-100: K = 3 clients, 266,610 params; the
GWMIN scorer's T * C(24, 3) candidate groups at M = 300, T = 35).  Mosaic
refuses what interpret mode accepts (a scalar read from ``pl.ANY`` memory, a
misaligned slice, too much VMEM), so these tests catch kernel faults before
any chip time is spent.  Nothing runs: the checks are that compilation
succeeds with ``interpret=False`` and that the compiled program holds a
``tpu_custom_call``.

The topology is described in a fixture, never at import time: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import aggregate, dorefa, sic_rates
from repro.kernels.platform import interpret_for_platform, resolve_interpret

LENET_PARAMS = 266_610          # LeNet-300-100 payload, elements per client
K = 3                           # paper: K = 3 clients per round
SCORER_GROUPS = 35 * 2024       # T = 35 rounds x C(24, 3) pool subsets


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # any failure means it cannot be described
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_mosaic(fn, *specs):
    compiled = jax.jit(fn).lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_weighted_aggregate_levels_compiles(one_chip):
    def fn(codes, scales, weights, levels):
        return aggregate.weighted_aggregate_pallas(
            codes, scales, weights, levels=levels, interpret=False
        )

    _assert_mosaic(
        fn,
        _spec((K, LENET_PARAMS), jnp.float32, one_chip),
        *[_spec((K,), jnp.float32, one_chip)] * 3,
    )


def test_weighted_aggregate_chunked_compiles(one_chip):
    n = 4 * aggregate.DEFAULT_CHUNK_ELEMS + 3      # ~8.4M params, 5 chunks

    def fn(codes, scales, weights, levels):
        return aggregate.weighted_aggregate_pallas(
            codes, scales, weights, levels=levels, interpret=False
        )

    _assert_mosaic(
        fn,
        _spec((K, n), jnp.float32, one_chip),
        *[_spec((K,), jnp.float32, one_chip)] * 3,
    )


def test_ota_aggregate_compiles(one_chip):
    def fn(deltas, coeff, noise):
        return aggregate.ota_aggregate_pallas(
            deltas, coeff, noise, interpret=False
        )

    _assert_mosaic(
        fn,
        _spec((K, LENET_PARAMS), jnp.float32, one_chip),
        _spec((K,), jnp.float32, one_chip),
        _spec((LENET_PARAMS,), jnp.float32, one_chip),
    )


def test_dorefa_quantize_compiles(one_chip):
    tile = dorefa.BLOCK_ROWS * dorefa.LANE
    rows = -(-LENET_PARAMS // tile) * dorefa.BLOCK_ROWS

    def fn(x2d, scale):
        return dorefa.quantize_codes_pallas(x2d, scale, 8, interpret=False)

    _assert_mosaic(
        fn,
        _spec((rows, dorefa.LANE), jnp.float32, one_chip),
        _spec((), jnp.float32, one_chip),
    )


def test_sic_scorer_compiles(one_chip):
    def fn(powers, gains, weights):
        return sic_rates.sic_weighted_rates_pallas(
            powers, gains, weights, 1e-13, interpret=False
        )

    _assert_mosaic(
        fn, *[_spec((SCORER_GROUPS, K), jnp.float32, one_chip)] * 3
    )


@pytest.mark.parametrize(
    "platform,interpret", [("tpu", False), ("cpu", True)]
)
def test_platform_picks_mosaic_on_tpu(platform, interpret):
    assert interpret_for_platform(platform) is interpret


def test_platform_rejects_unknown_backend():
    with pytest.raises(ValueError, match="'gpu'"):
        interpret_for_platform("gpu")


def test_explicit_interpret_overrides_platform():
    assert resolve_interpret(False) is False
    assert resolve_interpret(True) is True
    assert resolve_interpret(None) is interpret_for_platform(
        jax.default_backend()
    )


def test_no_kernel_entry_point_defaults_to_interpret():
    """Every public kernel takes ``interpret=None``: the platform decides."""
    import inspect

    from repro.kernels import flash_decode

    entry_points = [
        aggregate.weighted_aggregate_pallas, aggregate.ota_aggregate_pallas,
        dorefa.quantize_codes_pallas, dorefa.dequantize_codes_pallas,
        dorefa.quantize_dequantize_pallas,
        sic_rates.sic_weighted_rates_pallas, flash_decode.flash_decode_pallas,
    ]
    for fn in entry_points:
        default = inspect.signature(fn).parameters["interpret"].default
        assert default is None, fn.__name__
