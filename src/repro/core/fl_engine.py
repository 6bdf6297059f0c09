"""Batched FL round engine: one jitted dispatch per round (Algorithm 1).

The legacy round body in :mod:`repro.core.fl` runs one host-level
``local_update`` per scheduled device per round — K separate shard uploads,
K jitted SGD scans, K eager quantize passes, and a host ``tree_map``
aggregation — so simulation wall-clock is dominated by dispatch and scales
linearly in K.  This engine (``FLConfig.fl_engine = "batched"``) folds the
whole round body into a single jitted step over a device-resident
:class:`repro.data.client_bank.ClientBank`:

  1. **gather** — the round's K shards are a K-row gather of the bank's
     (M, n_batches, bs, D) tensors; no host round-trips.
  2. **local SGD** — ``vmap`` over the client axis of the same
     ``lax.scan`` epoch the legacy loop jits (:func:`sgd_epoch` is shared,
     so the per-client math is identical), producing all K deltas in one
     dispatch; the update-aware policies' ||delta||_2 signal becomes one
     batched reduction.
  3. **adaptive quantization** — per-client bit-widths are *traced*
     (``quantization.adaptive_bits`` on the (K,) budget vector, bit-identical
     to the legacy host ints) and the whole delta stack is DoReFa-quantized
     in the same jit via ``quantization.quantize_tree``'s (K,) bits mode.
  4. **aggregation** — the weighted FedAvg sum flows through an XLA einsum
     by default, or (``FLConfig.use_pallas``) through the fused
     dequant+aggregate Pallas kernel
     ``repro.kernels.aggregate.weighted_aggregate_pallas`` with per-client
     levels.

Scheduling, power allocation, rate/budget computation, timing, and logging
stay in the :mod:`repro.core.fl` driver and are shared with the legacy
engine, so both engines consume identical schedules, budgets and bit-widths.
(One caveat: for online ``needs_norms`` policies the selection feedback is
the update norm, whose batched reduction order differs from the legacy
per-device ``_tree_l2`` at the ulp level — a near-exact score tie between
two devices could in principle resolve differently.  Scores are continuous
functions of the channel draws, so exact ties do not occur in practice and
the equality grid pins schedule identity for ``update-aware``.  Before any
observation there is no feedback at all: every path — legacy, batched and
the traced online scan, whose carry seeds its norms with the same
constant — substitutes the policy's documented cold-start estimate
(``COLD_START_NORM``, see ``scheduling.UpdateAwarePolicy``), so round-0
selection reduces to best-channel on all of them;
tests/test_policy_scan.py pins this shared behavior.)  The legacy
loop remains the oracle the batched engine is pinned against
(``tests/test_fl_engine.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import compression as comp
from repro.core import noma
from repro.core import ota as ota_lib
from repro.core import power as power_lib
from repro.core import quantization as qlib
from repro.core import rates_jax
from repro.core import scheduling as sched_lib
from repro.data.client_bank import (
    BucketedClientBank, ClientBank, EvalBank, eval_sample_plan,
)
from repro.kernels.aggregate import weighted_aggregate_pallas

ENGINES = ("legacy", "batched")
# run_federated_learning round-body implementations; FLConfig validates
# ``fl_engine`` against this tuple.  "legacy" is the per-device host loop
# (the oracle), "batched" this module's one-dispatch-per-round engine.

HORIZON_MODES = ("per-round", "scan")
# fl.py driver modes; FLConfig validates ``horizon`` against this tuple.
# "per-round" dispatches one round at a time from the host; "scan" folds
# the whole horizon into ONE device program (a lax.scan over rounds),
# vmappable over seeds and shardable over a cell mesh.  Precomputed
# schedules run :func:`run_horizon` (the fl.py driver packs the schedule
# tensors up front); online policies with the traced protocol run
# :func:`run_horizon_online`, which folds selection, power allocation and
# the budget math into the scan body and threads the policy's
# FL-state feedback (norms/participation/ages) through the carry.
# Online policies *without* the traced protocol stay per-round only
# (errors.ERR_SCAN_ONLINE_POLICY).


# --------------------------------------------------------------------------
# Shared local-SGD epoch (the single source of the per-client math)
# --------------------------------------------------------------------------

def bound(params, base):
    """What a payload's ``batch_loss`` and ``accuracy`` read: its
    parameters, or ``(base, params)`` for a payload over a frozen base
    (``fl_models.frozen_base``).  The base is an operand of every program
    that trains or evaluates such a payload, passed and closed over but
    never differentiated, carried, vmapped or aggregated; ``base=None``
    (every payload that trains all its parameters) adds nothing to a
    program."""
    return params if base is None else (base, params)


def sgd_epoch(params, x, y, lr, *, model, unroll: int = 1, base=None):
    """One pass of minibatch SGD over a device's (padded) shard.

    x: (n_batches, bs, ...); y: (n_batches, bs, ...) with -1 marking
    padding in the label positions.  ``model`` is an
    :mod:`repro.models.fl_models` FLModel (hashable, rides as a jit
    static): its ``batch_loss(params, bx, by, valid)`` owns the per-batch
    loss, with ``valid = (by >= 0)`` as f32 precomputed here so image
    models mask exactly as the historical inlined LeNet loss did.  Both
    engines run exactly this function — the legacy loop jits it per device
    (``fl._sgd_epoch``), the batched engine vmaps it over the client axis —
    so an all-padding batch contributes an exactly-zero gradient and the
    two paths apply the same update sequence.  ``unroll`` feeds
    ``lax.scan`` (numerics-neutral); the batched engine unrolls a few steps
    to cut the per-step loop overhead its one-dispatch round pays K-fold.
    ``base`` is the payload's frozen base (see :func:`bound`).
    """

    def step(p, batch):
        bx, by, valid = batch

        def masked_loss(p_):
            return model.batch_loss(bound(p_, base), bx, by, valid)

        g = jax.grad(masked_loss)(p)
        new = jax.tree_util.tree_map(lambda w, gw: w - lr * gw, p, g)
        return new, None

    out, _ = jax.lax.scan(
        step, params, (x, y, (y >= 0).astype(jnp.float32)), unroll=unroll
    )
    return out


# --------------------------------------------------------------------------
# The jitted round step
# --------------------------------------------------------------------------

def _pallas_aggregate_leaf(leaf, bits_k, agg_w, *, compress, paper_exact):
    """Fused dequant + weighted sum of one client-stacked leaf.

    Quantizes the raw deltas to per-client integer codes (float32-held: b
    may reach 32, whose 2^32 - 1 levels overflow int32) and lets the Pallas
    kernel apply scale_k * w_k / a_k during the reduction, so the
    dequantized per-client tensors are never materialized.  A client with
    b >= 32 gets the same full-precision passthrough as every other
    quantization path (its kernel weight is zeroed and its raw delta joins
    via a separate einsum — under the paper-exact fixed [-1, 1] range the
    codes would otherwise clip it).  With ``compress=False`` the identity
    codes (scale = a = 1) reduce to the plain weighted sum.
    """
    k = leaf.shape[0]
    flat = leaf.reshape(k, -1).astype(jnp.float32)
    if compress:
        codes, scales, a = qlib.quantize_codes_batched(
            flat, bits_k,
            scales=jnp.ones((k,), jnp.float32) if paper_exact else None,
        )
        full = (bits_k >= 32).astype(jnp.float32)
        out = weighted_aggregate_pallas(
            codes, scales, agg_w * (1.0 - full), levels=a
        )
        out = out + jnp.einsum("k,kn->n", agg_w * full, flat)
    else:
        out = weighted_aggregate_pallas(
            flat, jnp.ones((k,), jnp.float32), agg_w,
            levels=jnp.ones((k,), jnp.float32),
        )
    return out.reshape(leaf.shape[1:])


def _sparse_quantize_aggregate(
    deltas, budgets, agg_w, *, payload, topk, paper_exact, use_pallas,
):
    """Top-k sparsification ∘ DoReFa over the concatenated update vector.

    Flattens the delta tree to one (K, P) matrix (sparsification picks
    coordinates of the *whole* payload, not per leaf), derives traced
    per-client (kept, bits) from the §IV budgets
    (:func:`repro.core.compression.topk_plan`), masks everything but each
    row's top-``kept`` magnitudes, DoReFa-quantizes the survivors, and
    reduces through the einsum or the (chunked) Pallas kernel.  The row
    max-abs scale is unchanged by masking (the largest-magnitude
    coordinate is always kept), so the codes match a quantize-of-masked
    oracle exactly.  Returns ``(update_tree, kept, bits)``.
    """
    leaves, treedef = jax.tree_util.tree_flatten(deltas)
    k = leaves[0].shape[0]
    sizes = [int(np.prod(leaf.shape[1:])) for leaf in leaves]
    flat = jnp.concatenate(
        [leaf.reshape(k, -1).astype(jnp.float32) for leaf in leaves], axis=1
    )                                            # (K, P)
    num_params = payload // 32
    kept, bits = comp.topk_plan(num_params, budgets, topk=topk)
    masked = flat * comp.topk_mask(flat, kept)

    scales_in = jnp.ones((k,), jnp.float32) if paper_exact else None
    codes, scales, a = qlib.quantize_codes_batched(masked, bits, scales=scales_in)
    full = (bits >= 32).astype(jnp.float32)
    if use_pallas:
        out = weighted_aggregate_pallas(
            codes, scales, agg_w * (1.0 - full), levels=a
        )
        out = out + jnp.einsum("k,kn->n", agg_w * full, masked)
    else:
        out = jnp.einsum("k,kn->n", agg_w * full, masked) + jnp.einsum(
            "k,kn->n", agg_w * (1.0 - full) / a * scales, codes
        )
    parts = jnp.split(out, np.cumsum(sizes)[:-1])
    update = jax.tree_util.tree_unflatten(
        treedef,
        [p.reshape(leaf.shape[1:]) for p, leaf in zip(parts, leaves)],
    )
    return update, kept, bits


def _train_quantize_aggregate(
    params, x, y, budgets, agg_w, gains_k, noise_key,
    *, lr, epochs, payload, compress, paper_exact, use_pallas, need_norms,
    model, topk, ota, ota_noise, ota_threshold, pmax, base=None,
):
    """The round body on gathered client rows: vmapped local SGD -> norms ->
    traced per-client quantization -> weighted aggregation.

    x: (K, nb, BS, ...); y: (K, nb, BS, ...).  The single implementation
    behind both the per-round jit (:func:`_round_step` gathers then calls
    this) and the scanned horizon (:func:`_horizon_core` calls it inside
    the ``lax.scan`` body) — the two drivers apply the identical update
    math, which is what the scan-vs-per-round equality grid pins.
    ``model`` (static FLModel) owns the per-batch loss; ``topk`` < 1
    routes compression through the top-k ∘ DoReFa stage.  Returns
    (new_params, bits (K,) int32, kept (K,) int32 — zeros when the sparse
    stage is off — and norms (K,) f32; zeros unless ``need_norms``).
    Zero-weight rows (``agg_w[k] = 0``: schedule padding in the scan path)
    still train but contribute exactly zero to the aggregate, so padded
    tail/empty rounds leave the parameters untouched.

    ``ota`` (static) swaps the digital quantize+aggregate stages for the
    over-the-air analog superposition (:func:`repro.core.ota.superpose_tree`
    — the noisy channel sum itself is the aggregate): ``gains_k`` (K,) are
    the round's channel amplitudes, ``noise_key`` (2,) uint32 seeds the
    receiver noise, and ota_noise / ota_threshold / pmax parameterize the
    signal model.  Outside OTA the two extra operands are dummy zeros the
    compiler drops (dead inputs), so the digital paths trace the identical
    program they always did; bits are logged as 32 (analog — nothing is
    quantized on air).

    ``base`` (a payload's frozen base, :func:`bound`) is shared by the K
    clients: closed over by the vmapped SGD, not mapped.
    """
    k = x.shape[0]

    def local_delta(xk, yk):
        new = params
        for _ in range(epochs):
            new = sgd_epoch(new, xk, yk, lr, model=model, unroll=8,
                            base=base)
        return jax.tree_util.tree_map(lambda a, b: a - b, new, params)

    deltas = jax.vmap(local_delta)(x, y)        # leaves (K, ...)

    if need_norms:
        # the policies' norm signal: raw pre-quantization deltas (one
        # batched reduction instead of K per-device _tree_l2 host syncs)
        sq = sum(
            jnp.sum(jnp.square(leaf.reshape(k, -1).astype(jnp.float32)), axis=1)
            for leaf in jax.tree_util.tree_leaves(deltas)
        )
        norms = jnp.sqrt(sq)
    else:
        norms = jnp.zeros((k,), jnp.float32)

    kept = jnp.zeros((k,), jnp.int32)

    if ota:
        update = ota_lib.superpose_tree(
            deltas, gains_k, agg_w, noise_key,
            pmax=pmax, noise_std=ota_noise, threshold=ota_threshold,
            use_pallas=use_pallas,
        )
        new_params = jax.tree_util.tree_map(
            lambda p, u: p + u, params, update
        )
        bits = jnp.full((k,), 32, jnp.int32)
        return new_params, bits, kept, norms

    if compress and topk < 1.0:
        update, kept, bits = _sparse_quantize_aggregate(
            deltas, budgets, agg_w, payload=payload, topk=topk,
            paper_exact=paper_exact, use_pallas=use_pallas,
        )
        new_params = jax.tree_util.tree_map(
            lambda p, u: p + u, params, update
        )
        return new_params, bits, kept, norms

    if compress:
        bits = qlib.adaptive_bits(payload, budgets)     # (K,) int32, traced
    else:
        bits = jnp.full((k,), 32, jnp.int32)

    if use_pallas:
        update = jax.tree_util.tree_map(
            lambda leaf: _pallas_aggregate_leaf(
                leaf, bits, agg_w, compress=compress, paper_exact=paper_exact
            ),
            deltas,
        )
    elif compress:
        # XLA mirror of the Pallas kernel: quantize to per-client codes and
        # fold the dequant scale s_k / a_k into the reduction coefficients,
        # so the dequantized per-client trees are never materialized.  Same
        # math as ``quantization.quantize_tree`` with (K,) bits followed by
        # the weighted einsum (modulo multiplication order), including the
        # per-client b >= 32 full-precision passthrough, which becomes a
        # second einsum over the raw deltas with complementary weights.
        a = qlib.dorefa_levels(bits)
        full = (bits >= 32).astype(jnp.float32)
        w_full = agg_w * full
        w_q = agg_w * (1.0 - full) / a

        def agg_leaf(leaf):
            flat = leaf.reshape(leaf.shape[0], -1).astype(jnp.float32)
            codes, scales, _ = qlib.quantize_codes_batched(
                flat, bits,
                scales=(
                    jnp.ones((leaf.shape[0],), jnp.float32)
                    if paper_exact else None
                ),
            )
            out = jnp.einsum("k,kn->n", w_full, flat) + jnp.einsum(
                "k,kn->n", w_q * scales, codes
            )
            return out.reshape(leaf.shape[1:])

        update = jax.tree_util.tree_map(agg_leaf, deltas)
    else:
        update = jax.tree_util.tree_map(
            lambda leaf: jnp.einsum("k,k...->...", agg_w, leaf), deltas
        )
    new_params = jax.tree_util.tree_map(lambda p, u: p + u, params, update)
    return new_params, bits, kept, norms


_ROUND_STATICS = (
    "lr", "epochs", "payload", "compress", "paper_exact",
    "use_pallas", "need_norms", "model", "topk",
    "ota", "ota_noise", "ota_threshold", "pmax",
)


@functools.partial(jax.jit, static_argnames=("nb",) + _ROUND_STATICS)
def _round_step(
    params, xb, yb, dev_idx, budgets, agg_w, gains_k, noise_key,
    *, nb, lr, epochs, payload, compress, paper_exact, use_pallas, need_norms,
    model, topk, ota, ota_noise, ota_threshold, pmax, base=None,
):
    """gather -> shared round body (:func:`_train_quantize_aggregate`).

    ``nb`` slices the bank's global batch grid down to the scheduled
    group's own max batch count (host-known per round), so the scan never
    pays for the cell-wide largest shard; batches past a client's own
    count are still all-padding and contribute exactly-zero gradients.
    Retraces once per distinct (group size K, nb) pair.
    """
    x = xb[dev_idx, :nb]                 # (K, nb, BS, ...)
    y = yb[dev_idx, :nb]                 # (K, nb, BS, ...)
    return _train_quantize_aggregate(
        params, x, y, budgets, agg_w, gains_k, noise_key,
        lr=lr, epochs=epochs, payload=payload,
        compress=compress, paper_exact=paper_exact, use_pallas=use_pallas,
        need_norms=need_norms, model=model, topk=topk, ota=ota,
        ota_noise=ota_noise, ota_threshold=ota_threshold, pmax=pmax,
        base=base,
    )


@functools.partial(jax.jit, static_argnames=_ROUND_STATICS)
def _round_step_gathered(
    params, x, y, budgets, agg_w, gains_k, noise_key,
    *, lr, epochs, payload, compress, paper_exact, use_pallas, need_norms,
    model, topk, ota, ota_noise, ota_threshold, pmax, base=None,
):
    """Round body on pre-gathered (K, nb, ...) rows — the bucketed-bank
    path, where the K-row gather spans several per-bucket banks and runs
    outside this jit (:meth:`BucketedClientBank.gather`).  Same body, so
    bucketed rounds are bit-identical to the padded bank's."""
    return _train_quantize_aggregate(
        params, x, y, budgets, agg_w, gains_k, noise_key,
        lr=lr, epochs=epochs, payload=payload,
        compress=compress, paper_exact=paper_exact, use_pallas=use_pallas,
        need_norms=need_norms, model=model, topk=topk, ota=ota,
        ota_noise=ota_noise, ota_threshold=ota_threshold, pmax=pmax,
        base=base,
    )


# --------------------------------------------------------------------------
# Scanned horizon: the whole precomputed-schedule simulation as ONE program
# --------------------------------------------------------------------------

_HORIZON_STATICS = (
    "nb", "lr", "epochs", "payload", "compress", "paper_exact", "use_pallas",
    "eval_full", "model", "topk", "ota", "ota_noise", "ota_threshold", "pmax",
)


def _horizon_core(
    params, dev_tk, budgets_tk, agg_tk, gains_tk, keys_t, eval_mask_t,
    eval_idx_tn, xb, yb, xe, ye,
    *, lr, epochs, payload, compress, paper_exact, use_pallas, eval_full,
    model, topk, ota, ota_noise, ota_threshold, pmax, base=None,
):
    """One whole horizon as a single ``lax.scan`` over rounds.

    The carry is the model parameters; per-round inputs are the
    precomputed-schedule tensors the fl.py driver packed on the host —
    dev_tk (T, K) int32 device ids (0-padded past each round's true group
    size), budgets_tk (T, K) f32 uplink bit budgets, agg_tk (T, K) f32
    FedAvg weights (zero on padding, which multiplies the padded rows out
    of the aggregate exactly), gains_tk (T, K) f32 channel amplitudes and
    keys_t (T, 2) uint32 receiver-noise keys (both consumed only under the
    OTA uplink; dummy zeros otherwise), eval_mask_t (T,) bool, and
    eval_idx_tn (T, n) eval-row gather plans (ignored when ``eval_full``).
    Emits the per-round (T, K) bit-widths, (T, K) kept-coordinate counts
    (zeros unless the top-k stage is on) and (T,) sampled test accuracies
    (NaN on rounds ``eval_mask_t`` skips — the host forward-fills,
    mirroring the per-round driver's repeated-accuracy logging under
    ``eval_every``).

    Un-jitted on purpose: :func:`run_horizon` jits it directly,
    :func:`run_horizon_vmapped` vmaps it over a seeds axis, and
    :func:`run_horizon_sharded` additionally shards a leading cell axis
    over a mesh — one implementation under all three transforms.
    ``base`` (a payload's frozen base, :func:`bound`) is closed over by
    the scan body: an operand of the program, not of the carry.
    """

    def body(p, inp):
        dev, bud, w, g, nk, do_eval, eidx = inp
        x = xb[dev]                     # (K, nb, BS, ...)
        y = yb[dev]                     # (K, nb, BS, ...)
        p2, bits, kept, _ = _train_quantize_aggregate(
            p, x, y, bud, w, g, nk, lr=lr, epochs=epochs, payload=payload,
            compress=compress, paper_exact=paper_exact,
            use_pallas=use_pallas, need_norms=False, model=model, topk=topk,
            ota=ota, ota_noise=ota_noise, ota_threshold=ota_threshold,
            pmax=pmax, base=base,
        )

        def ev(q):
            if eval_full:
                return model.accuracy(bound(q, base), xe, ye)
            return model.accuracy(bound(q, base), xe[eidx], ye[eidx])

        acc = jax.lax.cond(
            do_eval, ev, lambda q: jnp.asarray(jnp.nan, jnp.float32), p2
        )
        return p2, (bits, kept, acc)

    final, (bits_t, kept_t, acc_t) = jax.lax.scan(
        body, params,
        (dev_tk, budgets_tk, agg_tk, gains_tk, keys_t, eval_mask_t,
         eval_idx_tn),
    )
    return final, bits_t, kept_t, acc_t


@functools.partial(jax.jit, static_argnames=_HORIZON_STATICS)
def run_horizon(
    params, dev_tk, budgets_tk, agg_tk, gains_tk, keys_t, eval_mask_t,
    eval_idx_tn, xb, yb, xe, ye,
    *, nb, lr, epochs, payload, compress, paper_exact, use_pallas, eval_full,
    model, topk, ota, ota_noise, ota_threshold, pmax, base=None,
):
    """One precomputed-schedule horizon, one dispatch (see _horizon_core).

    ``nb`` slices the bank's batch grid to the horizon-wide max scheduled
    batch count (host-known, static) — the scan's shapes are fixed across
    rounds, so the per-round driver's per-group slicing becomes a single
    horizon-level slice; the extra all-padding batches contribute
    exactly-zero gradients.
    """
    return _horizon_core(
        params, dev_tk, budgets_tk, agg_tk, gains_tk, keys_t, eval_mask_t,
        eval_idx_tn, xb[:, :nb], yb[:, :nb], xe, ye,
        lr=lr, epochs=epochs, payload=payload, compress=compress,
        paper_exact=paper_exact, use_pallas=use_pallas, eval_full=eval_full,
        model=model, topk=topk, ota=ota, ota_noise=ota_noise,
        ota_threshold=ota_threshold, pmax=pmax, base=base,
    )


@functools.partial(jax.jit, static_argnames=_HORIZON_STATICS)
def run_horizon_vmapped(
    params_s, dev_stk, budgets_stk, agg_stk, gains_stk, keys_st, eval_mask_t,
    eval_idx_stn, xb, yb, xe, ye,
    *, nb, lr, epochs, payload, compress, paper_exact, use_pallas, eval_full,
    model, topk, ota, ota_noise, ota_threshold, pmax, base=None,
):
    """A whole seed sweep (S independent horizons), one dispatch.

    Leading axis S on params / schedule tensors / eval plans / noise keys;
    the client bank and test set are shared (the sweep varies channel
    draws, model init, schedules and receiver noise — not the data).
    ``eval_mask_t`` is shared too (eval cadence is a config, not a draw).
    Row s is the same program :func:`run_horizon` runs for that seed alone.
    """
    xbs, ybs = xb[:, :nb], yb[:, :nb]

    def one(p, d, b, a, g, nk, ei):
        return _horizon_core(
            p, d, b, a, g, nk, eval_mask_t, ei, xbs, ybs, xe, ye,
            lr=lr, epochs=epochs, payload=payload, compress=compress,
            paper_exact=paper_exact, use_pallas=use_pallas,
            eval_full=eval_full, model=model, topk=topk, ota=ota,
            ota_noise=ota_noise, ota_threshold=ota_threshold, pmax=pmax,
            base=base,
        )

    return jax.vmap(one)(
        params_s, dev_stk, budgets_stk, agg_stk, gains_stk, keys_st,
        eval_idx_stn,
    )


@functools.lru_cache(maxsize=None)
def _sharded_horizon_fn(
    shards, nb, lr, epochs, payload, compress, paper_exact, use_pallas,
    eval_full, model, topk, ota, ota_noise, ota_threshold, pmax,
):
    """Build (and cache) the jitted shard_map'd cell sweep for a mesh of
    ``shards`` local devices (the scheduler's vertex-reduction pattern,
    reapplied to whole simulations).  Only the leading cell axis is
    sharded; the client bank / test set are replicated and the cells never
    communicate — each mesh device runs its own (C/shards, S) block of
    vmapped horizons."""
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import cell_mesh
    from repro.sharding import rules

    mesh = cell_mesh(shards)
    axis = rules.CELL_AXIS

    def fn(params_cs, dev, bud, agg, gains, keys, emask, eidx, xb, yb, xe,
           ye):
        xbs, ybs = xb[:, :nb], yb[:, :nb]

        def per_seed(p, d, b, a, g, nk, ei):
            return _horizon_core(
                p, d, b, a, g, nk, emask, ei, xbs, ybs, xe, ye,
                lr=lr, epochs=epochs, payload=payload, compress=compress,
                paper_exact=paper_exact, use_pallas=use_pallas,
                eval_full=eval_full, model=model, topk=topk, ota=ota,
                ota_noise=ota_noise, ota_threshold=ota_threshold, pmax=pmax,
            )

        def per_cell(p, d, b, a, g, nk, ei):
            return jax.vmap(per_seed)(p, d, b, a, g, nk, ei)

        return jax.vmap(per_cell)(params_cs, dev, bud, agg, gains, keys, eidx)

    return jax.jit(jax.shard_map(
        fn, mesh=mesh,
        in_specs=rules.cell_sweep_in_specs(),
        out_specs=rules.cell_sweep_out_specs(),
        check_vma=False,
    ))


def _no_sharded_base(base):
    if base is not None:
        raise ValueError(
            "a payload over a frozen base runs its horizons on one device: "
            "the sharded cell sweep does not place the base on the mesh")


def run_horizon_sharded(
    params_cs, dev_cstk, budgets_cstk, agg_cstk, gains_cstk, keys_cst,
    eval_mask_t, eval_idx_cstn, xb, yb, xe, ye,
    *, shards, nb, lr, epochs, payload, compress, paper_exact, use_pallas,
    eval_full, model, topk, ota, ota_noise, ota_threshold, pmax, base=None,
):
    """A (C, S) cells-x-seeds sweep with the cell axis sharded over a mesh.

    C must be a multiple of ``shards`` (the fl.py driver pads and
    unpads).  With ``shards = 1`` this is exactly the double-vmapped
    single-device program, which the sharded-equality test pins the
    multi-device meshes against.  A payload over a frozen base has no
    sharded sweep: ``base`` must be None.
    """
    _no_sharded_base(base)
    fn = _sharded_horizon_fn(
        int(shards), int(nb), float(lr), int(epochs), int(payload),
        bool(compress), bool(paper_exact), bool(use_pallas), bool(eval_full),
        model, float(topk), bool(ota), float(ota_noise), float(ota_threshold),
        float(pmax),
    )
    return fn(
        params_cs, dev_cstk, budgets_cstk, agg_cstk, gains_cstk, keys_cst,
        eval_mask_t, eval_idx_cstn, xb, yb, xe, ye,
    )


# --------------------------------------------------------------------------
# Online-policy scanned horizons: selection + power + budgets in the scan
# --------------------------------------------------------------------------

_ONLINE_STATICS = _HORIZON_STATICS + (
    "scheduler", "pcfg", "uplink", "budget_scale", "need_norms",
)
# run_horizon_online's static kwargs: the precomputed-horizon statics plus
# the policy name (resolved through the registry at trace time — the
# registry entry, not a per-call instance, keys the jit cache), the
# hashable PolicyConfig (fl.py pins its non-physics fields so program
# identity depends only on K / power mode / cell physics), the uplink
# branch, the host-folded bandwidth*slot budget factor, and whether the
# policy consumes the norm feedback.


def _online_horizon_core(
    params, solo_tm, gains_tm, weights_m, sizes_m, keys_t, eval_mask_t,
    eval_idx_tn, xb, yb, xe, ye,
    *, scheduler, pcfg, uplink, budget_scale, need_norms, lr, epochs,
    payload, compress, paper_exact, use_pallas, eval_full, model, topk, ota,
    ota_noise, ota_threshold, pmax, base=None,
):
    """One whole *online-policy* horizon as a single ``lax.scan``.

    Where :func:`_horizon_core` consumes a host-precomputed schedule, this
    scan body runs the policy itself: per round it calls the traced
    selection protocol (``select_round_traced`` — masked ``lax.top_k``
    scoring or the matching-pursuit ``lax.while_loop``), allocates powers
    in closed form (``power.traced_round_powers``), prices the §IV uplink
    (``rates_jax.sic_rates`` — the same shifted-suffix-sum SIC math the
    fused GWMIN driver ``rates_jax.greedy_rounds_fused`` scores with — or
    ``noma.tdma_rates``), converts rates to bit budgets with the
    host-folded ``bandwidth * slot`` factor, and trains/aggregates through
    the same :func:`_train_quantize_aggregate` the precomputed scan uses.

    The carry is ``(params, TracedObservation)``: the policy's FL-state
    feedback — last update norms, participation counts, last-scheduled
    rounds — never leaves the device.  Carry updates scatter through
    ``where(mask, dev, M)`` indices: padding lanes point one past the end
    and JAX's default out-of-bounds-scatter drop discards them, so a
    padded lane aliasing device 0 can never corrupt device 0's state.
    The norm carry is seeded with the policy's ``COLD_START_NORM``
    (fl.py's driver builds the initial observation), though round-0
    selection only reads the participation zeros — the estimate
    convention substitutes the same constant either way.

    Emits per-round device ids, validity masks, bit-widths, kept counts
    and accuracies; the fl.py driver's single ``device_get`` of these is
    the horizon's ONE host sync, after which it rebuilds the f64 log
    tensors (rates/budgets/times) with the exact per-round host calls.
    """
    policy = sched_lib.get_policy(scheduler)
    num_devices = int(weights_m.shape[0])
    num_rounds = int(solo_tm.shape[0])
    t_arange = jnp.arange(num_rounds, dtype=jnp.int32)

    def body(carry, inp):
        p, obs = carry
        t, solo_row, g_row, nk, do_eval, eidx = inp
        dev, mask = policy.select_round_traced(
            t, solo_row, g_row, weights_m, obs, pcfg
        )
        maskf = mask.astype(jnp.float32)
        g_k = g_row[dev] * maskf
        w_k = weights_m[dev] * maskf
        p_k = power_lib.traced_round_powers(
            pcfg.power_mode, g_k, w_k, pcfg.pmax
        )
        if uplink == "tdma":
            rates_k = noma.tdma_rates(p_k, g_k, pcfg.noise_power)
        else:
            # noma and ota both log the shared-slot SIC rates; padding
            # lanes transmit zero power, receive zero rate/budget, and
            # sort behind every live lane in the SIC order
            rates_k = rates_jax.sic_rates(p_k, g_k, pcfg.noise_power)
        bud = rates_k * jnp.float32(budget_scale)
        raw = sizes_m[dev] * maskf
        agg = raw / jnp.maximum(jnp.sum(raw), 1.0)

        p2, bits, kept, norms_k = _train_quantize_aggregate(
            p, xb[dev], yb[dev], bud, agg, g_k, nk, lr=lr, epochs=epochs,
            payload=payload, compress=compress, paper_exact=paper_exact,
            use_pallas=use_pallas, need_norms=need_norms, model=model,
            topk=topk, ota=ota, ota_noise=ota_noise,
            ota_threshold=ota_threshold, pmax=pmax, base=base,
        )

        scat = jnp.where(mask, dev, num_devices)   # padding -> OOB, dropped
        part2 = obs.participation.at[scat].add(1, mode="drop")
        last2 = obs.last_round.at[scat].set(t, mode="drop")
        if need_norms:
            norms2 = obs.update_norms.at[scat].set(norms_k, mode="drop")
        else:
            norms2 = obs.update_norms
        obs2 = sched_lib.TracedObservation(norms2, part2, last2)

        def ev(q):
            if eval_full:
                return model.accuracy(bound(q, base), xe, ye)
            return model.accuracy(bound(q, base), xe[eidx], ye[eidx])

        acc = jax.lax.cond(
            do_eval, ev, lambda q: jnp.asarray(jnp.nan, jnp.float32), p2
        )
        return (p2, obs2), (dev, mask, bits, kept, acc)

    obs0 = sched_lib.TracedObservation.initial(
        num_devices, getattr(policy, "COLD_START_NORM", 1.0)
    )
    (final, _), (dev_tk, mask_tk, bits_t, kept_t, acc_t) = jax.lax.scan(
        body, (params, obs0),
        (t_arange, solo_tm, gains_tm, keys_t, eval_mask_t, eval_idx_tn),
    )
    return final, dev_tk, mask_tk, bits_t, kept_t, acc_t


@functools.partial(jax.jit, static_argnames=_ONLINE_STATICS)
def run_horizon_online(
    params, solo_tm, gains_tm, weights_m, sizes_m, keys_t, eval_mask_t,
    eval_idx_tn, xb, yb, xe, ye,
    *, nb, scheduler, pcfg, uplink, budget_scale, need_norms, lr, epochs,
    payload, compress, paper_exact, use_pallas, eval_full, model, topk, ota,
    ota_noise, ota_threshold, pmax, base=None,
):
    """One online-policy horizon, one dispatch (see _online_horizon_core).

    ``nb`` slices the bank to the *bank-wide* max batch count: unlike the
    precomputed scan the schedule is unknown up front, so every device
    must fit the gathered shape.  The extra all-padding batches contribute
    exactly-zero gradients (the same invariant :func:`run_horizon`
    documents), so the deltas — and the norms fed back to the policy —
    are bit-identical to the per-round engine's group-sliced ones.
    """
    return _online_horizon_core(
        params, solo_tm, gains_tm, weights_m, sizes_m, keys_t, eval_mask_t,
        eval_idx_tn, xb[:, :nb], yb[:, :nb], xe, ye,
        scheduler=scheduler, pcfg=pcfg, uplink=uplink,
        budget_scale=budget_scale, need_norms=need_norms, lr=lr,
        epochs=epochs, payload=payload, compress=compress,
        paper_exact=paper_exact, use_pallas=use_pallas, eval_full=eval_full,
        model=model, topk=topk, ota=ota, ota_noise=ota_noise,
        ota_threshold=ota_threshold, pmax=pmax, base=base,
    )


@functools.partial(jax.jit, static_argnames=_ONLINE_STATICS)
def run_horizon_online_vmapped(
    params_s, solo_stm, gains_stm, weights_m, sizes_m, keys_st, eval_mask_t,
    eval_idx_stn, xb, yb, xe, ye,
    *, nb, scheduler, pcfg, uplink, budget_scale, need_norms, lr, epochs,
    payload, compress, paper_exact, use_pallas, eval_full, model, topk, ota,
    ota_noise, ota_threshold, pmax, base=None,
):
    """An online-policy seed sweep (S independent horizons), one dispatch.

    Mirrors :func:`run_horizon_vmapped`: the per-seed axis carries the
    model inits, channel draws (and therefore solo tables) and noise keys;
    the data weights/sizes, eval cadence, client bank and test set are
    shared.  Row s is the same program :func:`run_horizon_online` runs for
    that seed alone.
    """
    xbs, ybs = xb[:, :nb], yb[:, :nb]

    def one(p, so, g, nk, ei):
        return _online_horizon_core(
            p, so, g, weights_m, sizes_m, nk, eval_mask_t, ei, xbs, ybs,
            xe, ye,
            scheduler=scheduler, pcfg=pcfg, uplink=uplink,
            budget_scale=budget_scale, need_norms=need_norms, lr=lr,
            epochs=epochs, payload=payload, compress=compress,
            paper_exact=paper_exact, use_pallas=use_pallas,
            eval_full=eval_full, model=model, topk=topk, ota=ota,
            ota_noise=ota_noise, ota_threshold=ota_threshold, pmax=pmax,
            base=base,
        )

    return jax.vmap(one)(params_s, solo_stm, gains_stm, keys_st, eval_idx_stn)


@functools.lru_cache(maxsize=None)
def _sharded_online_fn(
    shards, nb, scheduler, pcfg, uplink, budget_scale, need_norms, lr,
    epochs, payload, compress, paper_exact, use_pallas, eval_full, model,
    topk, ota, ota_noise, ota_threshold, pmax,
):
    """Build (and cache) the jitted shard_map'd *online* cell sweep —
    :func:`_sharded_horizon_fn` with the online core and its operand list
    (solo tables + channel rows instead of precomputed schedule tensors;
    the shared data weights/sizes replicated like the bank)."""
    from repro.launch.mesh import cell_mesh
    from repro.sharding import rules

    mesh = cell_mesh(shards)

    def fn(params_cs, solo, gains, keys, emask, eidx, weights_m, sizes_m,
           xb, yb, xe, ye):
        xbs, ybs = xb[:, :nb], yb[:, :nb]

        def per_seed(p, so, g, nk, ei):
            return _online_horizon_core(
                p, so, g, weights_m, sizes_m, nk, emask, ei, xbs, ybs,
                xe, ye,
                scheduler=scheduler, pcfg=pcfg, uplink=uplink,
                budget_scale=budget_scale, need_norms=need_norms, lr=lr,
                epochs=epochs, payload=payload, compress=compress,
                paper_exact=paper_exact, use_pallas=use_pallas,
                eval_full=eval_full, model=model, topk=topk, ota=ota,
                ota_noise=ota_noise, ota_threshold=ota_threshold, pmax=pmax,
            )

        def per_cell(p, so, g, nk, ei):
            return jax.vmap(per_seed)(p, so, g, nk, ei)

        return jax.vmap(per_cell)(params_cs, solo, gains, keys, eidx)

    return jax.jit(jax.shard_map(
        fn, mesh=mesh,
        in_specs=rules.cell_sweep_online_in_specs(),
        out_specs=rules.cell_sweep_online_out_specs(),
        check_vma=False,
    ))


def run_horizon_online_sharded(
    params_cs, solo_cstm, gains_cstm, keys_cst, eval_mask_t, eval_idx_cstn,
    weights_m, sizes_m, xb, yb, xe, ye,
    *, shards, nb, scheduler, pcfg, uplink, budget_scale, need_norms, lr,
    epochs, payload, compress, paper_exact, use_pallas, eval_full, model,
    topk, ota, ota_noise, ota_threshold, pmax, base=None,
):
    """A (C, S) online-policy cells-x-seeds sweep, cell axis sharded.

    Same contract as :func:`run_horizon_sharded`: C must be a multiple of
    ``shards`` (the fl.py driver pads and unpads), and ``shards = 1`` is
    exactly the double-vmapped single-device program.
    """
    _no_sharded_base(base)
    fn = _sharded_online_fn(
        int(shards), int(nb), scheduler, pcfg, uplink, float(budget_scale),
        bool(need_norms), float(lr), int(epochs), int(payload),
        bool(compress), bool(paper_exact), bool(use_pallas), bool(eval_full),
        model, float(topk), bool(ota), float(ota_noise), float(ota_threshold),
        float(pmax),
    )
    return fn(
        params_cs, solo_cstm, gains_cstm, keys_cst, eval_mask_t,
        eval_idx_cstn, weights_m, sizes_m, xb, yb, xe, ye,
    )


# --------------------------------------------------------------------------
# Engine front-end (what the fl driver calls)
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("model",))
def _eval_full(params, xe, ye, *, model, base=None):
    return model.accuracy(bound(params, base), xe, ye)


@functools.partial(jax.jit, static_argnames=("model",))
def _eval_sampled(params, xe, ye, idx, *, model, base=None):
    """Client-sampled test accuracy: gather the round's eval rows, forward
    once — the ClientBank gather idiom applied to evaluation."""
    return model.accuracy(bound(params, base), xe[idx], ye[idx])


class BatchedRoundEngine:
    """Round-body engine: builds the bank once, then one dispatch per round."""

    def __init__(self, dataset, shards, cfg, payload_bits: int, model=None):
        from repro.models.fl_models import frozen_base, get_fl_model

        self.cfg = cfg
        self.payload = int(payload_bits)
        self.model = model if model is not None else get_fl_model(cfg.model)
        self.base = frozen_base(self.model)
        bank_cls = (
            BucketedClientBank if cfg.client_bank == "bucketed" else ClientBank
        )
        self.bank = bank_cls.build(
            dataset.x_train, dataset.y_train, shards, cfg.batch_size
        )
        # Evaluation through the same gather idiom: test set resident on
        # device, per-round sampled rows precomputed (None = full eval,
        # bit-identical to the legacy accuracy over the raw test arrays).
        self.eval_bank = EvalBank.build(dataset.x_test, dataset.y_test)
        self._eval_idx = eval_sample_plan(
            self.eval_bank.num_samples, cfg.eval_sample, cfg.num_rounds,
            cfg.seed,
        )

    def evaluate(self, params, t: int) -> float:
        """Test accuracy after round t (sampled per ``FLConfig.eval_sample``).

        At ``eval_sample = 1`` this is the full-test-set accuracy, equal
        bit for bit to the legacy driver's ``model.accuracy`` call; below 1
        it evaluates the round's precomputed sample of test rows — the same
        (T, n) plan the scanned horizon consumes, so the two drivers report
        identical sampled accuracies.
        """
        if self._eval_idx is None:
            return float(_eval_full(
                params, self.eval_bank.xe, self.eval_bank.ye,
                model=self.model, base=self.base,
            ))
        return float(_eval_sampled(
            params, self.eval_bank.xe, self.eval_bank.ye,
            jnp.asarray(self._eval_idx[t]), model=self.model, base=self.base,
        ))

    def run_round(
        self, params, devs, budgets, agg_w, *, need_norms: bool, ota=None,
    ):
        """Run one round's local training + upload + aggregation.

        devs: scheduled device ids; budgets: per-device uplink bit budgets
        (the driver computed both — identically for either engine);
        agg_w: normalized FedAvg weights |D_k| / sum |D_k|.

        ``ota`` (dict or None) switches the upload to the over-the-air
        analog superposition: the driver passes ``gains`` (K,) channel
        amplitudes, ``key`` (2,) uint32 receiver-noise key and ``pmax``
        for the round (noise std / truncation threshold come from the
        config) and the aggregate becomes the noisy channel sum
        (:func:`repro.core.ota.superpose_tree`).

        Returns ``(params, bits, ratios, norms)`` with bits/ratios as
        np arrays matching the legacy per-round log entries and norms a
        list of floats (empty unless ``need_norms``).  With the top-k
        stage on, ``bits`` are the per-client DoReFa widths of the kept
        coordinates and ``ratios`` the honest sparse on-air ratios
        I / S_k (``compression.sparse_compression_ratio``).
        """
        k = len(devs)
        if k == 0:    # empty T*K > M tail round: nothing to train or send
            return params, np.zeros(0, np.int32), np.zeros(0), []
        cfg = self.cfg
        compress = cfg.compression == "adaptive"
        # slice the scan to this group's own max batch count (see _round_step)
        nb = self.bank.n_batches_for(devs)
        statics = dict(
            lr=float(cfg.learning_rate), epochs=int(cfg.local_epochs),
            payload=self.payload, compress=compress,
            paper_exact=bool(cfg.paper_exact_range),
            use_pallas=bool(cfg.use_pallas), need_norms=bool(need_norms),
            model=self.model, topk=float(cfg.topk),
        )
        if ota is not None:
            statics.update(
                ota=True, ota_noise=float(cfg.ota_noise),
                ota_threshold=float(cfg.ota_threshold),
                pmax=float(ota["pmax"]),
            )
            gains_dev = jnp.asarray(np.asarray(ota["gains"]), jnp.float32)
            key_dev = jnp.asarray(ota["key"])
        else:
            # fixed dummies: the digital paths never read them, and pinning
            # the statics avoids a retrace per (noise, threshold) config
            statics.update(
                ota=False, ota_noise=0.0, ota_threshold=0.0, pmax=0.0,
            )
            gains_dev = jnp.zeros((k,), jnp.float32)
            key_dev = jnp.zeros((2,), jnp.uint32)
        budgets_dev = jnp.asarray(np.asarray(budgets, np.float64))
        agg_dev = jnp.asarray(np.asarray(agg_w, np.float64), jnp.float32)
        if isinstance(self.bank, BucketedClientBank):
            x, y = self.bank.gather(devs, nb)
            params, bits, kept, norms = _round_step_gathered(
                params, x, y, budgets_dev, agg_dev, gains_dev, key_dev,
                base=self.base, **statics
            )
        else:
            params, bits, kept, norms = _round_step(
                params, self.bank.xb, self.bank.yb,
                jnp.asarray(devs, jnp.int32), budgets_dev, agg_dev,
                gains_dev, key_dev, nb=nb, base=self.base, **statics,
            )
        if compress and cfg.topk < 1.0:
            # honest sparse accounting: on-air size from the realized
            # (kept, bits) pair, not the dense 32-bit payload
            ratios = comp.sparse_compression_ratio(
                self.payload, np.asarray(kept), np.asarray(bits),
                self.payload // 32,
            )
        elif compress:
            # one vectorized call to the same helper the legacy loop runs
            # per device — identical f32 IEEE ops, so the recorded ratios
            # match the oracle's bit for bit
            ratios = np.asarray(
                qlib.compression_ratio(
                    self.payload, np.asarray(budgets, np.float64)
                ),
                np.float64,
            )
        else:
            ratios = np.ones(k)
        norms_out = [float(v) for v in np.asarray(norms)] if need_norms else []
        return params, np.asarray(bits), ratios, norms_out
