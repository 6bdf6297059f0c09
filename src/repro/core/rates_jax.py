"""Device-resident mirror of the batched SIC rate engine (jnp/XLA).

``repro.core.rates`` is the numpy control-plane engine the schedulers call
from inside Python greedy loops.  This module is the same math on the device
path, in three layers:

  * :func:`sic_rates` / :func:`batched_weighted_rates` — jnp mirrors of the
    numpy engine with identical decode-order semantics (descending receive
    power, ties broken by lower input index via a *stable* argsort) and the
    identical shifted-suffix-sum interference formulation, so numpy and jnp
    agree on which candidate subset wins an argmax.  Both broadcast over
    arbitrary leading batch axes; the MWIS greedy feeds a whole
    ``(T_rem, V, K)`` tensor of (round, candidate-subset) vertices at once.

  * :func:`greedy_step` — one jitted call per greedy step of the lazy GWMIN
    scheduler (``scheduling.lazy_greedy_schedule(backend="jax-stepwise")``).
    The C(pool, K) subset enumeration is built **once** on the host as
    position tuples into a per-round candidate pool; each step re-masks
    availability on device, re-ranks the pool by the precomputed solo-rate
    proxy, scores every (round, subset) vertex, and returns the argmax vertex
    plus the updated availability/done masks.  Nothing of size O(T*V) ever
    leaves the device, but each step still syncs scalars to the host.

  * :func:`greedy_rounds_fused` — the whole greedy selection loop as a
    single jitted ``lax.while_loop`` (``backend="jax"``, the default device
    path).  The carry is ``(step, feasible, avail_m, done_t, assign_tk)``:

        step      int32   greedy steps taken so far
        feasible  bool    last step found a finite-score vertex
        avail_m   (M,)    bool, device not yet scheduled
        done_t    (T,)    bool, round already assigned
        assign_tk (T, K)  int32 device ids, -1 where unassigned

    Each iteration re-ranks the candidate pools, scores the full (T, V, K)
    vertex tensor, takes the argmax vertex and writes it into ``assign_tk``
    — all on device.  The loop exits after min(T, M // K) steps or on the
    first infeasible step, and the caller syncs the final carry to the host
    exactly once per schedule (the T*K > M leftover tail falls back to the
    host path, as before).

    Two switches, both trace-time static:

      * ``scorer="xla"`` (default) scores vertices with
        :func:`weighted_rates_cmp`; ``scorer="pallas"`` lowers the same
        O(K^2) comparison-matrix math through the Pallas SIC kernel
        (``repro.kernels.sic_rates``).  The kernel accumulates in float32,
        so pallas-scored argmaxes can tie-flip vs the f64 XLA scorer on
        degenerate instances; the XLA scorer is the bit-identical-to-numpy
        path.
      * ``shards=N`` shards the V (candidate-subset) axis over the first N
        local devices via ``shard_map`` (``repro.sharding.vertex``; asking
        for more shards than there are local devices raises): every
        shard scores its slice of the enumeration and the global argmax is
        an in-mesh reduction — ``lax.pmax`` on the score, then ``lax.pmin``
        on the t-major global flat index among the maxima, then a ``psum``
        one-hot gather of the winning subset's device ids, preserving the
        host path's earliest-round / lexicographically-first tie-break
        exactly.  ``shards=None`` skips ``shard_map`` entirely.

Precision: the numpy engine is float64, so callers run this module under
``jax.enable_x64(True)`` (the scheduling driver does) to keep the
argmax tie-breaking bit-compatible with the host path.  Without x64 the same
code runs in float32 — fine for kernels, not for schedule equivalence.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

SCORERS = ("xla", "pallas")


def sic_rates(powers, gains, noise_power: float) -> jax.Array:
    """Per-device SIC spectral efficiencies, input order (jnp mirror).

    powers, gains: (..., K) arrays (any matching leading batch axes).
    Decode order = descending receive power, ties by lower input index
    (stable argsort) — identical to ``repro.core.rates.sic_rates``.
    """
    p = jnp.asarray(powers)
    g = jnp.asarray(gains)
    rx = p * g * g
    order = jnp.argsort(-rx, axis=-1, stable=True)
    rx_s = jnp.take_along_axis(rx, order, axis=-1)
    # Shifted suffix sum (not suffix - rx): bit-compatible with the numpy
    # engine, whose tail_i is exactly the cumsum partial at position i+1.
    suffix = jnp.cumsum(rx_s[..., ::-1], axis=-1)[..., ::-1]
    tail = jnp.concatenate(
        [suffix[..., 1:], jnp.zeros_like(suffix[..., :1])], axis=-1
    )
    rates_sorted = jnp.log2(1.0 + rx_s / (tail + noise_power))
    return jnp.put_along_axis(
        jnp.zeros_like(rates_sorted), order, rates_sorted, axis=-1,
        inplace=False,
    )


def batched_weighted_rates(powers, gains, weights, noise_power: float) -> jax.Array:
    """Weighted SIC sum rates over any leading batch axes: (..., K) -> (...).

    Sort-based exact mirror of the numpy engine; the kernels' jnp oracle
    (``repro.kernels.ref``) calls it on (V, K) rows.
    """
    w = jnp.asarray(weights)
    return jnp.sum(w * sic_rates(powers, gains, noise_power), axis=-1)


def weighted_rates_cmp(powers, gains, weights, noise_power: float) -> jax.Array:
    """Sort-free weighted SIC sum rates: (..., K) -> (...), K unrolled.

    The O(K^2) comparison-matrix form of the same decode order (descending
    receive power, ties to the lower index) used by the Pallas kernel
    (``repro.kernels.sic_rates``): interference for user i is the sum of
    receive powers decoded after it,

        tail_i = sum_j rx_j * [rx_j < rx_i or (rx_j == rx_i and j > i)].

    On CPU/TPU XLA this is pure elementwise work — 30x faster than the
    argsort/scatter mirror on the greedy's (T, V, K) vertex tensors, at the
    cost of a different interference summation *order* (input order instead
    of decode order), i.e. ULP-level differences from ``sic_rates``.  The
    greedy argmax is insensitive to those (distinct subsets are separated by
    far more than an ulp on any non-degenerate instance; the backend
    equivalence tests pin this).
    """
    p = jnp.asarray(powers)
    g = jnp.asarray(gains)
    w = jnp.asarray(weights)
    rx = p * g * g
    k = rx.shape[-1]
    acc = jnp.zeros(rx.shape[:-1], rx.dtype)
    for i in range(k):
        rxi = rx[..., i]
        tail = jnp.zeros_like(rxi)
        for j in range(k):
            if j == i:
                continue
            rxj = rx[..., j]
            decoded_after = (rxj < rxi) | ((rxj == rxi) & (j > i))
            tail = tail + jnp.where(decoded_after, rxj, 0.0)
        acc = acc + w[..., i] * jnp.log2(1.0 + rxi / (tail + noise_power))
    return acc


# --------------------------------------------------------------------------
# GWMIN greedy on device: shared vertex selection + step-wise / fused drivers
# --------------------------------------------------------------------------

def _score_vertices(g_tvk, w_tvk, pmax: float, noise_power: float, scorer: str):
    """(T, V, K) gains/weights -> (T, V) max-power weighted sum rates.

    ``scorer`` is trace-time static: "xla" runs :func:`weighted_rates_cmp`
    (f64 under x64, the bit-identical-to-numpy path); "pallas" flattens the
    vertex axes to one (T*V, K) candidate batch and runs the Pallas SIC
    comparison-matrix kernel (f32 accumulate — same decode order, ULP-level
    score differences).
    """
    if scorer == "xla":
        p_tvk = jnp.full(g_tvk.shape, pmax, g_tvk.dtype)
        return weighted_rates_cmp(p_tvk, g_tvk, w_tvk, noise_power)
    if scorer == "pallas":
        from repro.kernels import sic_rates as sic_kernel

        t_cnt, v_cnt, k = g_tvk.shape
        g_vk = g_tvk.reshape(t_cnt * v_cnt, k)
        w_vk = w_tvk.reshape(t_cnt * v_cnt, k)
        p_vk = jnp.full(g_vk.shape, pmax, g_vk.dtype)
        out = sic_kernel.sic_weighted_rates_pallas(p_vk, g_vk, w_vk, noise_power)
        return out.reshape(t_cnt, v_cnt).astype(g_tvk.dtype)
    raise ValueError(f"unknown scorer {scorer!r}; known: {SCORERS}")


def _select_vertex(
    gains_tm, weights_m, solo_tm, subs_pos_vk, avail_m, done_t,
    *, pool: int, pmax: float, noise_power: float, scorer: str = "xla",
    axis_name: str | None = None, n_shards: int = 1,
):
    """Argmax-weight (subset, round) vertex under the current masks.

    Per remaining round, the ``pool`` strongest available devices (by the
    solo-rate proxy, ties to the lower device id) form the candidate pool,
    sorted ascending by device id so ``subs_pos_vk``'s lexicographic position
    tuples map to the same subsets the numpy path enumerates.  Unavailable
    pool slots are pushed past ``n_valid`` with an id-M sentinel; any subset
    touching one (its last position, subsets being sorted) is masked to -inf,
    as are completed rounds.  The flat argmax is t-major / subset-lex-minor —
    the numpy path's exact tie-breaking (earliest round, first subset).

    With ``axis_name`` set, ``subs_pos_vk`` is this shard's slice of the
    enumeration (``n_shards`` slices of equal length, concatenated in lex
    order) and the argmax is combined across the mesh: pmax on the score,
    pmin on the t-major *global* flat index among score maxima, psum-gather
    of the (unique) owner shard's subset ids — bit-identical tie-breaking to
    the single-shard path.

    Returns ``(val, t_star, sub_ids)``; ``val == -inf`` means no feasible
    vertex remains.
    """
    t_cnt, m = gains_tm.shape
    v_cnt = subs_pos_vk.shape[0]
    solo_masked = jnp.where(avail_m[None, :], solo_tm, -jnp.inf)
    order = jnp.argsort(-solo_masked, axis=1, stable=True)[:, :pool]  # (T, pool)
    n_valid = jnp.minimum(jnp.sum(avail_m), pool)
    valid_slot = jnp.arange(pool)[None, :] < n_valid
    kept = jnp.where(valid_slot, order, m)          # sentinel id M past n_valid
    kept_sorted = jnp.sort(kept, axis=1)            # ascending ids, sentinels last
    safe_ids = jnp.minimum(kept_sorted, m - 1)
    g_pool = jnp.take_along_axis(gains_tm, safe_ids, axis=1)     # (T, pool)
    w_pool = weights_m[safe_ids]                                 # (T, pool)
    g_tvk = g_pool[:, subs_pos_vk]                               # (T, V, K)
    w_tvk = w_pool[:, subs_pos_vk]
    scores = _score_vertices(g_tvk, w_tvk, pmax, noise_power, scorer)  # (T, V)
    valid_v = subs_pos_vk[:, -1] < n_valid          # positions ascending per row
    ok = valid_v[None, :] & jnp.logical_not(done_t)[:, None]
    flat = jnp.where(ok, scores, -jnp.inf).reshape(-1)
    idx = jnp.argmax(flat)                          # first max: t-major order
    val = flat[idx]
    if axis_name is None:
        t_star = idx // v_cnt
        sub_ids = kept_sorted[t_star, subs_pos_vk[idx % v_cnt]]  # (K,)
        return val, t_star, sub_ids
    # Sharded combine: the local argmax is the shard's minimal global flat
    # index among its maxima (local and global flat orders agree within a
    # shard), so pmin over index candidates recovers the global first max.
    t_local = idx // v_cnt
    v_local = idx % v_cnt
    v_total = v_cnt * n_shards
    shard = jax.lax.axis_index(axis_name)
    gidx = t_local * v_total + shard * v_cnt + v_local
    vmax = jax.lax.pmax(val, axis_name)
    sentinel = jnp.asarray(t_cnt * v_total, gidx.dtype)
    cand = jnp.where(val == vmax, gidx, sentinel)
    gbest = jax.lax.pmin(cand, axis_name)
    t_star = gbest // v_total
    sub_local = kept_sorted[t_local, subs_pos_vk[v_local]]
    # (t, shard, v_local) -> gidx is injective, so exactly one shard owns
    # gbest; a psum of the masked ids is a one-hot gather across the mesh.
    mine = cand == gbest
    sub_ids = jax.lax.psum(jnp.where(mine, sub_local, 0), axis_name)
    return vmax, t_star, sub_ids


@functools.partial(
    jax.jit, static_argnames=("pool", "pmax", "noise_power")
)
def greedy_step(
    gains_tm: jax.Array,     # (T, M) channel gains, whole horizon
    weights_m: jax.Array,    # (M,) device weights
    solo_tm: jax.Array,      # (T, M) solo-rate pool-ranking proxy (host f64)
    subs_pos_vk: jax.Array,  # (V, K) int32 subsets as pool *positions*, lex order
    avail_m: jax.Array,      # (M,) bool: device not yet scheduled
    done_t: jax.Array,       # (T,) bool: round already assigned
    *,
    pool: int,
    pmax: float,
    noise_power: float,
):
    """One GWMIN greedy step: argmax-weight (subset, round) vertex on device.

    See :func:`_select_vertex` for the pool ranking / masking / tie-break
    rules.  ``pool`` is clamped to M like the host driver clamps
    ``candidate_pool`` — a caller passing ``pool > M`` gets the full-cell
    enumeration semantics instead of a shape error; subsets whose positions
    reach past the clamped pool are masked infeasible.

    Returns (best_val, t_star, subset_device_ids, avail_new, done_new); a
    best_val of -inf means no feasible vertex (caller stops or falls back to
    the host tail path for leftover groups smaller than K).
    """
    pool = min(pool, gains_tm.shape[1])
    val, t_star, sub_ids = _select_vertex(
        gains_tm, weights_m, solo_tm, subs_pos_vk, avail_m, done_t,
        pool=pool, pmax=pmax, noise_power=noise_power,
    )
    feasible = val > -jnp.inf
    # Out-of-range sentinel scatters are dropped by jax; the where() guards
    # the infeasible case anyway.
    avail_new = jnp.where(feasible, avail_m.at[sub_ids].set(False), avail_m)
    done_new = jnp.where(feasible, done_t.at[t_star].set(True), done_t)
    return val, t_star, sub_ids, avail_new, done_new


def _fused_loop(
    gains_tm, weights_m, solo_tm, subs_pos_vk,
    *, pool: int, pmax: float, noise_power: float, scorer: str,
    axis_name: str | None = None, n_shards: int = 1,
):
    """The whole greedy selection loop as one ``lax.while_loop`` (see module
    docstring for the carry layout).  Shared by the single-device jit and
    each ``shard_map`` shard — under sharding the collectives inside
    ``_select_vertex`` make every element of the carry replicated, so all
    shards run identical trip counts."""
    t_cnt, m = gains_tm.shape
    kk = subs_pos_vk.shape[1]
    max_steps = min(t_cnt, m // kk)   # static: the step-wise driver's
                                      # `avail_count >= kk` bound

    def cond(carry):
        step, feasible, _avail, _done, _assign = carry
        return (step < max_steps) & feasible

    def body(carry):
        step, _feasible, avail, done, assign = carry
        val, t_star, sub_ids = _select_vertex(
            gains_tm, weights_m, solo_tm, subs_pos_vk, avail, done,
            pool=pool, pmax=pmax, noise_power=noise_power, scorer=scorer,
            axis_name=axis_name, n_shards=n_shards,
        )
        feasible = val > -jnp.inf
        avail = jnp.where(feasible, avail.at[sub_ids].set(False), avail)
        done = jnp.where(feasible, done.at[t_star].set(True), done)
        assign = jnp.where(
            feasible, assign.at[t_star].set(sub_ids.astype(assign.dtype)), assign
        )
        return (step + jnp.int32(1), feasible, avail, done, assign)

    init = (
        jnp.int32(0),
        jnp.asarray(True),
        jnp.ones(m, bool),
        jnp.zeros(t_cnt, bool),
        jnp.full((t_cnt, kk), -1, jnp.int32),
    )
    _steps, _feasible, avail, done, assign = jax.lax.while_loop(cond, body, init)
    return assign, done, avail


@functools.partial(
    jax.jit, static_argnames=("pool", "pmax", "noise_power", "scorer")
)
def _fused_single(gains_tm, weights_m, solo_tm, subs_pos_vk,
                  *, pool, pmax, noise_power, scorer):
    return _fused_loop(
        gains_tm, weights_m, solo_tm, subs_pos_vk,
        pool=pool, pmax=pmax, noise_power=noise_power, scorer=scorer,
    )


@functools.lru_cache(maxsize=None)
def _fused_sharded(shards: int, pool: int, pmax: float, noise_power: float,
                   scorer: str):
    """Build (and cache) the jitted shard_map'd fused loop for a mesh of
    ``shards`` local devices.  The whole while_loop runs inside shard_map:
    only the subset enumeration is sharded; gains/weights/solo and the
    carry are replicated (the in-mesh argmax reduction keeps them so)."""
    from jax.sharding import PartitionSpec as P

    from repro.sharding import vertex as vertex_lib

    mesh = vertex_lib.vertex_mesh(shards)
    axis = vertex_lib.VERTEX_AXIS

    def fn(gains_tm, weights_m, solo_tm, subs_local):
        return _fused_loop(
            gains_tm, weights_m, solo_tm, subs_local,
            pool=pool, pmax=pmax, noise_power=noise_power, scorer=scorer,
            axis_name=axis, n_shards=shards,
        )

    return jax.jit(jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(), P(), P(), P(axis, None)),
        out_specs=(P(), P(), P()),
        check_vma=False,
    ))


def greedy_rounds_fused(
    gains_tm: jax.Array,     # (T, M) channel gains, whole horizon
    weights_m: jax.Array,    # (M,) device weights
    solo_tm: jax.Array,      # (T, M) solo-rate pool-ranking proxy (host f64)
    subs_pos_vk: jax.Array,  # (V, K) int32 subsets as pool *positions*, lex order
    *,
    pool: int,
    pmax: float,
    noise_power: float,
    scorer: str = "xla",
    shards: int | None = None,
):
    """Run the entire GWMIN greedy selection on device; sync-free until the
    caller reads the result (one host sync per schedule).

    Returns ``(assign_tk, done_t, avail_m)``: the (T, K) int32 assignment
    tensor (-1 where unassigned; rows with ``done_t`` hold exactly K device
    ids), the completed-round mask, and the still-available-device mask the
    host tail path resumes from when T*K > M.

    ``scorer`` picks the vertex scorer ("xla" | "pallas"); ``shards=N``
    shards the V axis over N local devices via ``shard_map`` (see module
    docstring) and raises when fewer are present.  ``pool`` must already
    be clamped to M by the caller (the scheduling driver does) so the
    position enumeration matches the ranked pools.
    """
    if scorer not in SCORERS:
        raise ValueError(f"unknown scorer {scorer!r}; known: {SCORERS}")
    if shards is None:
        return _fused_single(
            gains_tm, weights_m, solo_tm, subs_pos_vk,
            pool=pool, pmax=pmax, noise_power=noise_power, scorer=scorer,
        )
    from repro.core import errors
    from repro.sharding import vertex as vertex_lib

    n = int(shards)
    available = vertex_lib.max_vertex_shards()
    if n > available:
        raise ValueError(errors.ERR_SHARDS_EXCEED_DEVICES.format(
            option="shards", requested=n, available=available,
        ))
    pad = vertex_lib.pad_rows_to_multiple(subs_pos_vk.shape[0], n)
    if pad:
        # Sentinel rows point at position ``pool``: past every ranked pool,
        # so ``valid_v`` masks them infeasible on every shard.
        subs_pos_vk = jnp.concatenate([
            subs_pos_vk,
            jnp.full((pad, subs_pos_vk.shape[1]), pool, subs_pos_vk.dtype),
        ])
    fn = _fused_sharded(n, pool, float(pmax), float(noise_power), scorer)
    return fn(gains_tm, weights_m, solo_tm, subs_pos_vk)
