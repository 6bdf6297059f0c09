"""Over-the-air (OTA) analog aggregation physics (Bereyhi et al. 2206.06679).

A fundamentally different uplink from the paper's digital NOMA/TDMA: every
scheduled device transmits its *raw* model update simultaneously over the
shared slot, scaled so the channel itself computes the FedAvg sum.  The PS
receives the noisy analog superposition

    y = sum_{k in A} h_k b_k delta_k + n,        n ~ N(0, sigma_ota^2 I)

and never decodes a per-device payload — DoReFa quantization and top-k
sparsification are structurally bypassed (``FLConfig`` rejects the combos).

Truncated channel inversion sets the transmit amplitudes: device k sends
``b_k = sqrt(eta) * w_k / h_k`` (w_k its FedAvg weight), so each participant
contributes exactly ``sqrt(eta) * w_k * delta_k`` after the channel.  The
participation set A drops devices whose channel is too weak to invert —
``h_k >= threshold * max_{j} h_j`` — and the power scalar eta is pinned by
the §IV per-device budget: the transmit power of device k is
``eta * w_k^2 * ||delta_k||^2 / h_k^2 <= pmax``, so

    eta = min_{k in A} pmax * h_k^2 / (w_k^2 * ||delta_k||^2)

(the binding device transmits at exactly pmax).  The PS estimate is

    theta_update = ( sum_{k in A} w_k delta_k  +  n / sqrt(eta) ) / sum_{k in A} w_k

— at ``noise_std = 0`` and ``threshold = 0`` this is exactly the weighted
FedAvg aggregate; growing noise or truncation trades accuracy for power.

Everything here is traced JAX math shared verbatim by the batched per-round
engine, the scanned horizon and the legacy oracle driver
(:func:`superpose_tree` is the single aggregation operator all three call),
with the receiver noise drawn from a dedicated seeded stream
(:func:`horizon_keys`) so per-round and scanned drivers consume identical
draws.  Airtime: OTA rounds charge one shared uplink slot, exactly like
NOMA's (``fl._round_physics``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import errors

UPLINK_MODES = ("noma", "tdma", "ota")
# fl.run_federated_learning uplink modes; FLConfig validates ``uplink``
# against this tuple ("noma"/"tdma" are the paper's digital §IV uplinks,
# "ota" the analog superposition subsystem of this module).

OTA_SEED_OFFSET = 29
# decorrelates the receiver-noise stream from the model-init / channel
# streams (FLConfig.seed), the scheduling permutation (+17,
# scheduling.RandomPolicy.SEED_OFFSET) and the eval sampler (+23,
# client_bank.EVAL_SEED_OFFSET)

_TINY = 1e-30   # divide guard; far below any realized f32 weight sum


def check_uplink(uplink: str, *, compression: str, topk: float,
                 power_mode: str) -> None:
    """The uplink-combination rules, shared by ``FLConfig.__post_init__``
    and the fl.py drivers (the uplink can also arrive as a call-site
    argument overriding ``cfg.uplink``).  Raises ValueError with pinned
    messages on incoherent combos."""
    if uplink not in UPLINK_MODES:
        raise ValueError(
            errors.ERR_UNKNOWN_UPLINK.format(uplink=uplink, modes=UPLINK_MODES)
        )
    if uplink == "ota":
        if topk < 1.0:
            raise ValueError(errors.ERR_OTA_TOPK)
        if compression != "none":
            raise ValueError(errors.ERR_OTA_COMPRESSION)
        if power_mode == "mapel":
            raise ValueError(errors.ERR_OTA_MAPEL)
    elif power_mode == "ota-align":
        raise ValueError(errors.ERR_OTA_ALIGN_UPLINK)


def round_keys(seed: jax.Array, num_rounds: int) -> jax.Array:
    """(T, 2) uint32 ``fold_in(PRNGKey(seed), t)`` for t < T, traceable:
    ``seed`` may be a traced integer (``jax.random.PRNGKey`` maps it to
    the key a Python int of the same value gives)."""
    base = jax.random.PRNGKey(seed)
    return jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
        base, jnp.arange(num_rounds)
    )


_round_keys = jax.jit(round_keys, static_argnums=1)


def horizon_keys(seed: int, num_rounds: int) -> np.ndarray:
    """(T, 2) uint32 per-round receiver-noise keys.

    ``fold_in(PRNGKey(seed + OTA_SEED_OFFSET), t)``, one program and one
    read back — threefry is deterministic, so the per-round driver
    (indexing row t) and the scanned horizon (consuming the stack as scan
    inputs) draw bit-identical noise.
    """
    return np.asarray(
        _round_keys(np.int64(int(seed) + OTA_SEED_OFFSET), num_rounds)
    )


def superpose_flat(
    flat: jax.Array,        # (K, P) raw client update rows
    gains_k: jax.Array,     # (K,) channel amplitudes h_k at this round
    agg_w: jax.Array,       # (K,) FedAvg weights (0 marks padding rows)
    key: jax.Array,         # (2,) uint32 receiver-noise key
    *,
    pmax: float,
    noise_std: float,
    threshold: float,
    use_pallas: bool = False,
) -> jax.Array:
    """The OTA receiver estimate for one round; returns the (P,) update.

    Implements the module-docstring signal model end to end: participation
    mask (traced — zero-weight padding rows and sub-threshold channels drop
    out), power-budget eta, analog superposition, receiver noise scaled by
    1/sqrt(eta), and the 1/sum(w_A) renormalization.  Rounds with no
    participants (all-padding scan rows) return exactly zero.  The weighted
    reduction runs through the XLA einsum or, under ``use_pallas``, the
    fused scale+superpose+denoise Pallas kernel
    (:func:`repro.kernels.aggregate.ota_aggregate_pallas`).
    """
    from repro.kernels.aggregate import ota_aggregate_pallas

    k, p = flat.shape
    flat = flat.astype(jnp.float32)
    h = gains_k.astype(jnp.float32)
    w = agg_w.astype(jnp.float32)

    cand = w > 0.0
    hmax = jnp.max(jnp.where(cand, h, 0.0), initial=0.0)
    mask = cand & (h > 0.0) & (h >= jnp.float32(threshold) * hmax)

    energy = jnp.sum(flat * flat, axis=1)               # (K,) ||delta_k||^2
    # per-participant eta cap: pmax h_k^2 / (w_k^2 ||delta_k||^2); a
    # zero-energy delta imposes no cap (its transmit power is zero anyway)
    den = w * w * energy
    cap = jnp.where(
        mask & (den > 0.0),
        jnp.float32(pmax) * h * h / jnp.maximum(den, _TINY),
        jnp.inf,
    )
    eta = jnp.min(cap, initial=jnp.inf)

    wsum = jnp.sum(jnp.where(mask, w, 0.0))
    wsafe = jnp.maximum(wsum, _TINY)
    coeff = jnp.where(mask, w, 0.0) / wsafe             # (K,)

    # receiver noise, referred through the channel inversion: n / (sqrt(eta)
    # * sum w).  eta = inf (no participant caps the budget: empty round or
    # all-zero deltas) means no finite-power transmission constrains the
    # noise referral — the update is exactly the noiseless sum (zero).
    scale = jnp.where(
        jnp.isfinite(eta) & (eta > 0.0),
        jnp.float32(noise_std) / (jnp.sqrt(eta) * wsafe),
        0.0,
    )
    noise = scale * jax.random.normal(key, (p,), jnp.float32)

    if use_pallas:
        return ota_aggregate_pallas(flat, coeff, noise)
    return jnp.einsum("k,kn->n", coeff, flat) + noise


@functools.partial(
    jax.jit,
    static_argnames=("pmax", "noise_std", "threshold", "use_pallas"),
)
def superpose_tree(
    deltas, gains_k, agg_w, key,
    *, pmax: float, noise_std: float, threshold: float,
    use_pallas: bool = False,
):
    """OTA aggregation of a client-stacked delta tree (leaves (K, ...)).

    THE shared aggregation operator: the batched engine and the scanned
    horizon call it inside their round body, the legacy oracle stacks its
    host-loop deltas and calls it directly — one jitted computation, so the
    three drivers apply bit-identical aggregation math to a given delta
    stack.  Flattens the tree to one (K, P) matrix first (eta depends on
    the *whole* payload's energy, not per-leaf), superposes, splits back.
    Returns the update tree (leaves shaped like ``deltas`` minus the K
    axis).
    """
    leaves, treedef = jax.tree_util.tree_flatten(deltas)
    k = leaves[0].shape[0]
    sizes = [int(np.prod(leaf.shape[1:])) for leaf in leaves]
    flat = jnp.concatenate(
        [leaf.reshape(k, -1).astype(jnp.float32) for leaf in leaves], axis=1
    )
    out = superpose_flat(
        flat, gains_k, agg_w, key, pmax=pmax, noise_std=noise_std,
        threshold=threshold, use_pallas=use_pallas,
    )
    parts = jnp.split(out, np.cumsum(sizes)[:-1])
    return jax.tree_util.tree_unflatten(
        treedef,
        [part.reshape(leaf.shape[1:]) for part, leaf in zip(parts, leaves)],
    )
