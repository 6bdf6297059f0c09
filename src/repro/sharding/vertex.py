"""1-D device mesh over the scheduler's vertex axis.

The device-resident MWIS greedy (``repro.core.rates_jax``) scores a
(T, V, K) tensor of (round, candidate-subset) vertices per step.  For
multi-device cells the V axis is embarrassingly parallel: each device
scores its own slice of the subset enumeration and the per-shard argmaxes
are combined with in-mesh collectives (``lax.pmax`` on the score,
``lax.pmin`` on the t-major global flat index, so the numpy path's
earliest-round / lexicographically-first tie-break survives sharding).

This module owns the mesh plumbing so ``rates_jax`` stays mesh-agnostic;
it is the scheduler-side sibling of ``repro.sharding.rules`` (which maps
model parameter axes, not scheduler work).
"""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import Mesh

VERTEX_AXIS = "v"


def max_vertex_shards() -> int:
    """Upper bound on useful vertex shards: the local device count."""
    return jax.local_device_count()


def vertex_mesh(shards: int) -> Mesh:
    """1-D mesh of the first ``shards`` local devices, axis ``"v"``.

    ``shards`` must be in [1, local_device_count()]; the scheduler raises
    ``errors.ERR_SHARDS_EXCEED_DEVICES`` before asking for more devices
    than the host has.
    """
    if not 1 <= shards <= jax.local_device_count():
        raise ValueError(
            f"vertex_mesh needs 1 <= shards <= {jax.local_device_count()} "
            f"local devices (got {shards})"
        )
    devices = np.asarray(jax.local_devices()[:shards])
    return Mesh(devices, (VERTEX_AXIS,))


def pad_rows_to_multiple(rows: int, shards: int) -> int:
    """Rows of padding needed so ``rows`` divides evenly across ``shards``."""
    return (-rows) % shards
