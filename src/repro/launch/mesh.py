"""Production mesh construction (MULTI-POD DRY-RUN step 1).

A function, not a module constant: importing this module never touches jax
device state. Single pod: (data=16, model=16) = 256 chips (one v5e pod).
Multi-pod: (pod=2, data=16, model=16) = 512 chips; the leading "pod" axis
carries the cross-pod data-parallel (gradient all-reduce) traffic.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_smoke_mesh(devices=None):
    """1-device mesh with the same axis names (CPU tests)."""
    return jax.make_mesh((1, 1), ("data", "model"), devices=devices)


def cell_mesh(shards: int):
    """1-D mesh of the first ``shards`` local devices over the FL
    simulator's cell axis (``repro.sharding.rules.CELL_AXIS``).

    The multi-cell sweep shards whole independent simulations over it
    (``fl_engine.run_horizon_sharded``); like the scheduler's vertex mesh,
    ``fl.run_cell_sweep`` raises before asking for more devices than the
    host has.
    """
    import numpy as np
    from jax.sharding import Mesh

    from repro.sharding.rules import CELL_AXIS

    if not 1 <= shards <= jax.local_device_count():
        raise ValueError(
            f"cell_mesh needs 1 <= shards <= {jax.local_device_count()} "
            f"local devices (got {shards})"
        )
    return Mesh(np.asarray(jax.local_devices()[:shards]), (CELL_AXIS,))
