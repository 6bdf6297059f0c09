"""User scheduling policies for FL over NOMA (paper §III + online variants).

Every scheduler is a **policy** behind one protocol (:class:`SchedulerPolicy`):

    state = policy.init_state(gains_tm, weights_m, cfg)          # once
    group, state = policy.select_round(t, state, obs)            # per round

``cfg`` is a :class:`PolicyConfig` (group size K, power mode, cell physics,
seed); ``obs`` is an :class:`Observation` carrying the *online* observables —
previous-round local-update norms, per-device participation counts /
last-participation ages, and realized uplink rates.  Policies come in two
flavours:

  * **precomputed** (``online = False``): device selection depends only on
    the channel realizations, so ``init_state`` plans the whole T-round
    horizon up front (the paper's setting).  ``select_round`` just replays
    the plan and ignores ``obs``.
  * **online** (``online = True``): selection reads FL state from ``obs``
    round by round; ``fl.run_federated_learning`` calls ``select_round``
    *inside* the training loop (live mode) and feeds the realized norms /
    rates back.  Online policies may re-schedule a device across rounds
    (``respects_c1 = False``) — they trade the paper's one-shot C1
    constraint for long-horizon participation control.

Online policies may additionally implement the **traced protocol**
(``traced_protocol = True`` plus ``init_traced`` / ``select_round_traced``):
a jnp mirror of ``select_round`` that runs *inside* the scanned-horizon
round body (``fl_engine._online_horizon_core``), reading a
:class:`TracedObservation` threaded through the ``lax.scan`` carry — the
whole feedback loop stays on device, so ``FLConfig.horizon = "scan"``
accepts the policy (config validation asks :func:`policy_is_traced`).
All three registered online policies implement it.

Policies are looked up by name through a registry (:func:`register_policy` /
:func:`get_policy`); power allocation and rate computation live in one shared
finalization step (:func:`finalize_schedule` for full horizons,
:func:`finalize_round` for live mode) built on
:class:`repro.core.power.PowerAllocator`.

Registered policies
-------------------
  * ``lazy-gwmin`` — graph-free Algorithm 2 (GWMIN MWIS greedy); numpy or
    device-resident jax backend.  The paper's proposed scheduler.
  * ``literal-gwmin`` — Algorithm 2 on the explicit C(M,K)*T-vertex graph
    (exact fidelity, exponential memory; M up to ~12).
  * ``random`` / ``round-robin`` / ``proportional-fair`` — the §IV / ref [6]
    baselines (PF ranks by the weighted solo rate w_k R_k; ``by_gain=True``
    reproduces the seed's raw-gain ranking).
  * ``update-aware`` — online; scores devices by ‖ΔW_k‖ · solo rate
    (Amiri et al., arXiv:2001.10402) so informative *and* fast uplinks win.
  * ``age-fair`` — online; staleness-boosted weighted rates
    (1 + age_k) · w_k R_k (Yang et al., arXiv:1908.06287) so no device
    starves over long horizons.
  * ``matching-pursuit`` — online; greedily grows the round's device set by
    residual aggregation-error decrease (the OTA companion policy: omitted
    devices cost their weighted update energy, admitted devices pay the
    channel-inversion noise penalty lambda * max (w n / h)^2 with
    lambda = ota_noise^2 / pmax).  With ``ota_noise = 0`` it degenerates
    to top-K by weighted update norm.

How to add a policy
-------------------
1. Write a class with ``init_state`` / ``select_round`` (subclass
   ``_PrecomputedPolicy`` for offline plans or ``_ScoreTopKPolicy`` for
   online top-K scoring rules — then it is one ``_plan`` / ``_score``
   method).  Declare ``online`` and ``respects_c1`` (and, for online
   policies, ``needs_norms`` — whether the FL loop should compute
   per-device update norms for you; it defaults to True when absent).
   To run under ``horizon="scan"`` an online policy also implements the
   traced protocol (``_ScoreTopKPolicy`` subclasses inherit it from a
   jnp ``_score_traced`` mirror of ``_score``); without it the scanned
   driver keeps rejecting the policy with the pinned error.
2. Decorate it with ``@register_policy("my-policy")``.  The name becomes a
   valid ``FLConfig.scheduler`` immediately (config validation reads the
   registry), and ``benchmarks/fig6_schemes.py`` can sweep it by name.
3. If it is online, return groups from ``select_round`` using only
   ``state`` + ``obs``; the runtime owns power allocation and rates via the
   shared finalization (never allocate powers inside a policy).

MWIS formulation (paper §III-A)
-------------------------------
A vertex v = (S, t) is a K-subset S proposed for round t; edges connect
vertices violating C1 (shared device, t_i != t_j) or C2 (t_i == t_j).  An
independent set with T vertices is a complete schedule; vertex weight
w(v) = sum_{k in S} w_k R_k^t makes the MWIS the max-weighted-sum-rate
schedule (Eq. 9-10).

Equivalence note (DESIGN.md §6.3): in the residual graph after any number of
GWMIN removals, the remaining vertex set is always {all K-subsets of unused
devices} x {remaining rounds}, and every vertex has the *same* degree
beta = (C(A,K)-1) + (T_rem-1) * (C(A,K) - C(A-K,K)), where A = #unused
devices. With uniform degrees, argmax_{v in Q} w(v)/(beta(v)+1) reduces to
argmax_v w(v) (the global max-weight vertex is always in Q since
sum_{u in J(v)} w(u)/(beta+1) <= beta*w(v)/(beta+1) + w(v)/(beta+1) = w(v)).
So Algorithm 2 == repeatedly take the max-weight (subset, round) among unused
devices and remaining rounds. ``tests/test_scheduling.py`` checks the two
produce identical schedules on instances where the literal graph fits.

Backends: ``lazy_greedy_schedule(backend="numpy")`` (default) walks rounds in
Python and scores each round's candidate batch with the numpy engine;
``backend="jax"`` runs the **entire** selection loop on device as one jitted
``lax.while_loop`` (``repro.core.rates_jax.greedy_rounds_fused``): the
C(pool, K) subset enumeration is built once as *positions* into a per-round
candidate pool, the loop carries ``(step, feasible, avail, done, assign)``
on device, every iteration re-masks availability, re-ranks the pools, scores
the full (T, V, K) vertex tensor, and writes the argmax vertex into the
(T, K) assignment tensor, and the host syncs exactly once per schedule.
Two fused-backend switches: ``scorer="xla" | "pallas"`` picks the vertex
scorer (XLA comparison-matrix vs the Pallas SIC kernel of
``repro.kernels.sic_rates``) and ``shards=N`` shards the subset axis over N
local devices via ``shard_map`` with an in-mesh argmax reduction
(``repro.sharding.vertex``).  ``backend="jax-stepwise"`` keeps the previous
driver — one jitted ``greedy_step`` call (and one host sync) per greedy
step.  All backends produce bit-identical schedules (same stable
tie-breaking: earliest round, lexicographically-first subset, ties in the
pool ranking to the lower device id); leftover tail groups smaller than K
fall back to the host path.  Power refinement with ``power_mode="mapel"``
is batched over all selected groups at the end (``power.mapel_batched``)
instead of solved round-by-round.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Callable, NamedTuple, Protocol, Sequence

import numpy as np

from repro.core import power as power_lib
from repro.core import rates as rates_lib
from repro.utils import spans

PowerFn = Callable[[np.ndarray, np.ndarray], np.ndarray]
# (gains_K, weights_K) -> powers_K; may carry a ``batched`` attribute
# (gains_VK, weights_VK) -> powers_VK for vectorized candidate scoring.
# ``power.PowerAllocator`` satisfies this interface.

SCHEDULER_BACKENDS = ("numpy", "jax", "jax-stepwise")
# the lazy greedy's drivers (_lazy_gwmin_rounds); FLConfig validates
# ``scheduler_backend`` against this same tuple.  "jax" is the fused
# while_loop driver (one host sync per schedule); "jax-stepwise" keeps the
# one-jitted-call-per-greedy-step driver for comparison and benchmarks.


# --------------------------------------------------------------------------
# Shared helpers
# --------------------------------------------------------------------------

def make_power_fn(
    mode: str, pmax: float, noise_power: float
) -> power_lib.PowerAllocator:
    """Legacy-named front door to :class:`repro.core.power.PowerAllocator`.

    The allocator is callable and carries ``batched`` (an alias of
    ``solve_batched``), so it drops into every historical ``PowerFn`` call
    site; new code should use ``power.make_power_allocator`` directly.
    """
    return power_lib.make_power_allocator(mode, pmax, noise_power)


def _solo_proxy(gains, weights, pmax: float, noise_power: float) -> np.ndarray:
    """Pool-ranking proxy: weighted interference-free rate of each device
    alone.  Shared by the numpy per-round pool and the jax backend's
    precomputed (T, M) table — the backends' bit-equality rests on ranking
    from identical float64 values, so there is exactly one formula."""
    return weights * np.log2(1.0 + (pmax * gains**2) / noise_power)


def _batched_powers(power_fn: PowerFn, gains_vk, weights_vk) -> np.ndarray:
    """(V, K) powers for V candidate groups; row loop only for iterative
    allocators (MAPEL) that expose no vectorized form."""
    batched = getattr(power_fn, "batched", None)
    if batched is not None:
        return batched(gains_vk, weights_vk)
    return np.stack(
        [power_fn(g, w) for g, w in zip(gains_vk, weights_vk)]
    )


def score_subsets(
    subsets_vk: np.ndarray,
    t: int,
    gains_tm: np.ndarray,
    weights_m: np.ndarray,
    power_fn: PowerFn,
    noise_power: float,
) -> np.ndarray:
    """Weighted sum rate of every candidate group in one engine call.

    subsets_vk: (V, K) int array of device ids, one candidate K-subset per
    row, all proposed for round t. Replaces the seed's per-subset Python
    loop (one ``group_weighted_rate`` call per ``itertools.combinations``
    element) with a single (V, K) ``batched_weighted_rates`` evaluation.
    """
    if subsets_vk.size == 0:
        return np.zeros((len(subsets_vk),))
    g = gains_tm[t][subsets_vk]
    w = weights_m[subsets_vk]
    p = _batched_powers(power_fn, g, w)
    return rates_lib.batched_weighted_rates(p, g, w, noise_power)


def group_weighted_rate(
    subset: Sequence[int],
    t: int,
    gains_tm: np.ndarray,
    weights_m: np.ndarray,
    power_fn: PowerFn,
    noise_power: float,
):
    """Weighted sum rate (and powers, rates) of scheduling `subset` at round t."""
    idx = np.asarray(subset, dtype=np.intp)
    g = gains_tm[t, idx]
    w = weights_m[idx]
    p = power_fn(g, w)
    rates = rates_lib.sic_rates(p, g, noise_power)
    return float(np.sum(w * rates)), p, rates


def _rates(powers, gains, noise_power):
    """Thin wrapper kept for back-compat; the math lives in core.rates."""
    return rates_lib.sic_rates(powers, gains, noise_power)


def validate_group(group, num_devices: int, k: int, *, label: str = "group"):
    """One round's group invariants: size <= K, distinct, in-range ids.

    The single owner of the per-round rules — ``Schedule.validate`` applies
    it to every round and the live FL loop applies it to each group an
    online policy hands back.  Raises ValueError.
    """
    if (
        len(group) > k
        or len(set(group)) != len(group)
        or any(not 0 <= d < num_devices for d in group)
    ):
        raise ValueError(
            f"invalid {label} {tuple(group)}: at most K={k} distinct "
            f"device ids in [0, {num_devices})"
        )


@dataclasses.dataclass
class Schedule:
    """A complete schedule: device groups, powers and rates per round."""

    rounds: list            # list[T] of tuple[int, ...] device ids
    powers: list            # list[T] of np.ndarray (K,)
    rates: list             # list[T] of np.ndarray (K,) spectral efficiencies
    weighted_sum_rate: float
    method: str
    allow_revisits: bool = False   # True for schedules built by online
                                   # policies (respects_c1 = False)

    def scheduled_devices(self) -> set:
        return set(itertools.chain.from_iterable(self.rounds))

    def validate(self, num_devices: int, k: int, allow_revisits=None):
        """Assert constraints C2 (and C1 unless revisits are allowed) hold.

        ``allow_revisits=None`` defers to the schedule's own flag (set by
        ``build_schedule`` from the producing policy's ``respects_c1``).
        Online policies legitimately re-schedule devices across rounds;
        they still may not duplicate a device within a round or emit
        out-of-range ids.
        """
        if allow_revisits is None:
            allow_revisits = self.allow_revisits
        seen = set()
        for t, grp in enumerate(self.rounds):
            validate_group(grp, num_devices, k, label=f"round-{t} group")
            for d in grp:
                if not allow_revisits and d in seen:
                    raise ValueError(
                        f"C1 violated: device {d} scheduled again in round "
                        f"{t} (set allow_revisits for online-policy schedules)"
                    )
                seen.add(d)
        return True


def finalize_round(group, t, gains_tm, weights_m, power_fn, noise_power):
    """Power allocation + SIC rates for one scheduled group (live mode).

    The per-round twin of :func:`finalize_schedule`: online policies select
    a group inside the FL loop and the runtime finalizes it immediately —
    policies themselves never allocate power.  Returns ``(powers, rates)``,
    both (len(group),), input order.
    """
    idx = np.asarray(group, dtype=np.intp)
    if idx.size == 0:
        return np.zeros(0), np.zeros(0)
    g = gains_tm[t, idx]
    w = weights_m[idx]
    p = np.asarray(power_fn(g, w))
    r = rates_lib.sic_rates(p, g, noise_power)
    return p, r


def finalize_schedule(rounds, gains_tm, weights_m, power_fn, noise_power, method):
    """Powers/rates/weighted-sum for a complete schedule.

    The shared finalization step: every policy's selected rounds pass
    through here, so power allocation and rate computation have exactly one
    owner.  Groups are batched by size and handed to the allocator in one
    call per size (for MAPEL this is the batched polyblock refinement over
    all T selected groups — the per-round loop it replaces solved each
    group separately).  Tail groups smaller than K (T*K > M horizons) and
    empty rounds batch among themselves.
    """
    num_rounds = len(rounds)
    powers, rates = [None] * num_rounds, [None] * num_rounds
    vals = np.zeros(num_rounds)
    by_size = {}
    for t, grp in enumerate(rounds):
        by_size.setdefault(len(grp), []).append(t)
    for kk, ts in sorted(by_size.items()):
        idx = np.array([rounds[t] for t in ts], dtype=np.intp).reshape(len(ts), kk)
        g = gains_tm[np.asarray(ts, dtype=np.intp)[:, None], idx]
        w = weights_m[idx]
        if kk == 0:
            p = np.zeros((len(ts), 0))
        else:
            with spans.span("fl.power"):
                p = _batched_powers(power_fn, g, w)
        r = rates_lib.sic_rates(p, g, noise_power)
        for row, t in enumerate(ts):
            powers[t] = p[row]
            rates[t] = r[row]
            vals[t] = float(np.sum(w[row] * r[row]))
    total = 0.0
    for t in range(num_rounds):    # accumulate in round order (reproducible)
        total += float(vals[t])
    return Schedule(list(map(tuple, rounds)), powers, rates, total, method)


_finalize = finalize_schedule    # back-compat alias (pre-policy-API name)


# --------------------------------------------------------------------------
# Literal Algorithm 2 on the explicit scheduling graph
# --------------------------------------------------------------------------

@dataclasses.dataclass
class SchedulingGraph:
    vertices: list          # list of (subset tuple, t)
    weights: np.ndarray     # (V,)
    adjacency: list         # list[V] of set[int]

    def degree(self, v: int) -> int:
        return len(self.adjacency[v])


def build_scheduling_graph(
    gains_tm: np.ndarray,
    weights_m: np.ndarray,
    k: int,
    power_fn: PowerFn,
    noise_power: float,
) -> SchedulingGraph:
    """Explicit graph with C(M,K)*T vertices (paper §III-A)."""
    num_rounds, num_devices = gains_tm.shape
    subsets = list(itertools.combinations(range(num_devices), k))
    vertices = [(subset, t) for t in range(num_rounds) for subset in subsets]
    subs_vk = np.array(subsets, dtype=np.intp).reshape(len(subsets), k)
    weights = np.concatenate(
        [
            score_subsets(subs_vk, t, gains_tm, weights_m, power_fn, noise_power)
            for t in range(num_rounds)
        ]
    )
    adjacency = [set() for _ in vertices]
    for i, (si, ti) in enumerate(vertices):
        set_i = set(si)
        for j in range(i + 1, len(vertices)):
            sj, tj = vertices[j]
            if ti == tj or set_i & set(sj):
                adjacency[i].add(j)
                adjacency[j].add(i)
    return SchedulingGraph(vertices, weights, adjacency)


def gwmin_mwis(graph: SchedulingGraph) -> list:
    """Algorithm 2: greedy maximum-weight independent set (GWMIN).

    Returns selected vertex indices. J(v) = v and its neighbours; beta(v) the
    degree; Q = {v : w(v) >= sum_{u in J(v)} w(u)/(beta(u)+1)};
    v* = argmax_{v in Q} w(v)/(beta(v)+1).
    """
    alive = set(range(len(graph.vertices)))
    adj = {v: set(graph.adjacency[v]) for v in alive}
    w = graph.weights
    selected = []
    while alive:
        beta = {v: len(adj[v]) for v in alive}
        q = []
        for v in alive:
            closed = adj[v] | {v}
            thresh = sum(w[u] / (beta[u] + 1) for u in closed)
            if w[v] >= thresh - 1e-12:
                q.append(v)
        if not q:  # theoretical fallback; GWMIN guarantees Q nonempty
            q = list(alive)
        v_star = max(q, key=lambda v: w[v] / (beta[v] + 1))
        selected.append(v_star)
        remove = adj[v_star] | {v_star}
        alive -= remove
        for v in alive:
            adj[v] -= remove
    return selected


def _literal_gwmin_rounds(gains_tm, weights_m, k, power_fn, noise_power):
    """Selection step of the literal Algorithm 2 (graph build + GWMIN)."""
    graph = build_scheduling_graph(gains_tm, weights_m, k, power_fn, noise_power)
    chosen = gwmin_mwis(graph)
    rounds = [()] * gains_tm.shape[0]
    for v in chosen:
        subset, t = graph.vertices[v]
        rounds[t] = subset
    return rounds


def literal_graph_schedule(
    gains_tm, weights_m, k, *, power_mode="max", pmax=0.01, noise_power=1e-13
) -> Schedule:
    """Paper-exact Algorithm 2 (explicit graph). Small M only."""
    power_fn = make_power_fn(power_mode, pmax, noise_power)
    rounds = _literal_gwmin_rounds(gains_tm, weights_m, k, power_fn, noise_power)
    return finalize_schedule(
        rounds, gains_tm, weights_m, power_fn, noise_power, "literal-gwmin"
    )


# --------------------------------------------------------------------------
# Lazy (scalable) equivalent of Algorithm 2
# --------------------------------------------------------------------------

def _best_subset_for_round(
    t, avail, gains_tm, weights_m, k, power_fn, noise_power, candidate_pool, pmax
):
    """Best K-subset of `avail` for round t.

    Exact when len(avail) is small; otherwise enumerates subsets of the
    ``candidate_pool`` strongest devices (by singleton weighted rate), which
    preserves the greedy's behaviour in practice (weak devices never enter
    the argmax group). All C(pool, K) candidates are scored in a single
    batched rate-engine call; ties keep the lexicographically first subset,
    matching the seed's sequential strict-improvement loop.
    """
    avail = np.asarray(sorted(avail))
    if len(avail) > candidate_pool:
        # Stable sort so proxy ties keep the lower device id — the rule the
        # jax backend's masked ranking uses, keeping the backends identical.
        solo = _solo_proxy(gains_tm[t, avail], weights_m[avail], pmax, noise_power)
        keep = avail[np.argsort(-solo, kind="stable")[:candidate_pool]]
    else:
        keep = avail
    kk = min(k, len(keep))
    subs_vk = np.array(
        list(itertools.combinations(sorted(keep.tolist()), kk)), dtype=np.intp
    ).reshape(-1, kk)
    if len(subs_vk) == 0:
        return -np.inf, None
    vals = score_subsets(subs_vk, t, gains_tm, weights_m, power_fn, noise_power)
    i_best = int(np.argmax(vals))
    return float(vals[i_best]), tuple(subs_vk[i_best].tolist())


def _greedy_rounds_numpy(
    gains_tm, weights_m, k, search_fn, noise_power, candidate_pool, pmax,
    *, rounds=None, avail=None, remaining=None,
):
    """Host-path greedy selection loop (also the jax backend's tail path).

    Mutates/returns ``rounds`` (list[T] of tuples); ``avail``/``remaining``
    default to the full device/round sets so the jax driver can hand over
    mid-schedule state when fewer than K devices remain.
    """
    num_rounds, num_devices = gains_tm.shape
    if rounds is None:
        rounds = [()] * num_rounds
    if avail is None:
        avail = set(range(num_devices))
    if remaining is None:
        remaining = set(range(num_rounds))
    while remaining and len(avail) > 0:
        # max-weight vertex across all remaining rounds
        best = (-np.inf, None, None)
        for t in sorted(remaining):
            val, sub = _best_subset_for_round(
                t, avail, gains_tm, weights_m, k, search_fn, noise_power,
                candidate_pool, pmax,
            )
            if val > best[0]:
                best = (val, sub, t)
        _, subset, t = best
        if subset is None:
            break
        rounds[t] = subset
        avail -= set(subset)
        remaining.discard(t)
    return rounds


def _jax_greedy_inputs(gains_tm, weights_m, candidate_pool, k, pmax, noise_power):
    """Shared prologue of both jax drivers: clamp the pool to M, enumerate
    the C(pool, kk) subsets once as pool *positions* (lex order), and build
    the pool-ranking proxy with the *host* engine so every backend ranks
    candidate pools from identical float64 values."""
    num_devices = gains_tm.shape[1]
    pool = int(min(candidate_pool, num_devices))
    kk = min(k, pool)
    subs_pos = np.array(
        list(itertools.combinations(range(pool), kk)), dtype=np.int32
    ).reshape(-1, kk)
    solo_tm = _solo_proxy(gains_tm, weights_m[None, :], pmax, noise_power)
    return pool, kk, subs_pos, solo_tm


def _jax_greedy_tail(
    rounds, avail_np, done_np,
    gains_tm, weights_m, k, search_fn, noise_power, candidate_pool, pmax,
):
    """Shared epilogue of both jax drivers: once fewer than K devices remain
    (T*K > M horizons), the host loop finishes the leftover smaller groups —
    the device enumeration is fixed-K, and those tail steps are
    O(C(K-1, kk)) cheap."""
    avail_host = set(np.flatnonzero(avail_np).tolist())
    remaining_host = set(np.flatnonzero(~done_np).tolist())
    if avail_host and remaining_host:
        _greedy_rounds_numpy(
            gains_tm, weights_m, k, search_fn, noise_power, candidate_pool,
            pmax, rounds=rounds, avail=avail_host, remaining=remaining_host,
        )
    return rounds


def _greedy_rounds_jax_stepwise(
    gains_tm, weights_m, k, search_fn, noise_power, candidate_pool, pmax
):
    """Device-path greedy selection: one jitted argmax call per step.

    Each step ``rates_jax.greedy_step`` re-masks availability and scores the
    whole (T, V, K) vertex tensor on device, but the loop itself walks on
    the host — every step syncs the argmax scalars back (the fused driver
    below removes exactly that).  Runs under x64 so scores (and therefore
    argmax tie-breaking) line up with the float64 host path.
    """
    import jax
    import jax.numpy as jnp

    from repro.core import rates_jax

    num_rounds, num_devices = gains_tm.shape
    pool, kk, subs_pos, solo_tm = _jax_greedy_inputs(
        gains_tm, weights_m, candidate_pool, k, pmax, noise_power
    )
    rounds = [()] * num_rounds
    with jax.enable_x64(True):
        jg = jnp.asarray(gains_tm, jnp.float64)
        jw = jnp.asarray(weights_m, jnp.float64)
        jsolo = jnp.asarray(solo_tm, jnp.float64)
        jsubs = jnp.asarray(subs_pos)
        avail = jnp.ones(num_devices, bool)
        done = jnp.zeros(num_rounds, bool)
        avail_count = num_devices
        steps = 0
        while steps < num_rounds and avail_count >= kk:
            val, t_star, sub_ids, avail, done = rates_jax.greedy_step(
                jg, jw, jsolo, jsubs, avail, done,
                pool=pool, pmax=float(pmax), noise_power=float(noise_power),
            )
            if not bool(val > -jnp.inf):
                break
            rounds[int(t_star)] = tuple(int(d) for d in np.asarray(sub_ids))
            avail_count -= kk
            steps += 1
        avail_np = np.asarray(avail)
        done_np = np.asarray(done)
    return _jax_greedy_tail(
        rounds, avail_np, done_np,
        gains_tm, weights_m, k, search_fn, noise_power, candidate_pool, pmax,
    )


def _greedy_rounds_jax_fused(
    gains_tm, weights_m, k, search_fn, noise_power, candidate_pool, pmax,
    *, scorer="xla", shards=None,
):
    """Device-path greedy selection, fully fused: the entire GWMIN loop runs
    inside one jitted ``lax.while_loop`` (``rates_jax.greedy_rounds_fused``)
    and the host syncs exactly once per schedule, pulling the (T, K)
    assignment tensor plus the avail/done masks the T*K > M tail path
    resumes from.  ``scorer`` picks the vertex scorer (XLA comparison-matrix
    vs the Pallas SIC kernel); ``shards`` shards the subset axis over local
    devices — see the ``rates_jax`` module docstring for both switches.
    Runs under x64 so scores (and therefore argmax tie-breaking) line up
    with the float64 host path.
    """
    import jax
    import jax.numpy as jnp

    from repro.core import rates_jax

    num_rounds, num_devices = gains_tm.shape
    pool, kk, subs_pos, solo_tm = _jax_greedy_inputs(
        gains_tm, weights_m, candidate_pool, k, pmax, noise_power
    )
    rounds = [()] * num_rounds
    with jax.enable_x64(True):
        assign, done, avail = rates_jax.greedy_rounds_fused(
            jnp.asarray(gains_tm, jnp.float64),
            jnp.asarray(weights_m, jnp.float64),
            jnp.asarray(solo_tm, jnp.float64),
            jnp.asarray(subs_pos),
            pool=pool, pmax=float(pmax), noise_power=float(noise_power),
            scorer=scorer, shards=shards,
        )
        # the one host sync per schedule
        with spans.span("fl.sync"):
            assign_np, done_np, avail_np = jax.device_get(
                (assign, done, avail)
            )
    for t in np.flatnonzero(done_np):
        rounds[t] = tuple(int(d) for d in assign_np[t])
    return _jax_greedy_tail(
        rounds, avail_np, done_np,
        gains_tm, weights_m, k, search_fn, noise_power, candidate_pool, pmax,
    )


def lazy_greedy_schedule(
    gains_tm,
    weights_m,
    k,
    *,
    power_mode="max",
    pmax=0.01,
    noise_power=1e-13,
    candidate_pool=24,
    backend="numpy",
    scorer="xla",
    shards=None,
) -> Schedule:
    """Graph-free Algorithm 2 (see module docstring for the equivalence).

    ``candidate_pool`` bounds the per-round enumeration to the pool of
    strongest devices; the batched rate engine scores all C(pool, K)
    candidates in one call, so pools of 24-64 are cheap (the seed's
    per-subset loop capped practical pools at ~16).

    ``backend="jax"`` runs the whole selection loop on the device path as a
    single fused ``lax.while_loop`` (one host sync per schedule; see module
    docstring) and produces bit-identical schedules; use it for M >> 300.
    ``backend="jax-stepwise"`` keeps the one-jitted-call-per-greedy-step
    driver it replaced (still bit-identical, syncs every step).  ``scorer``
    and ``shards`` tune the fused backend only: the vertex scorer
    ("xla" | "pallas" SIC kernel) and the number of local devices the
    subset axis is sharded over (None = no shard_map).

    With power_mode="mapel" the subset *search* runs at max power and MAPEL
    refines only the selected groups — batched over all T groups in one
    ``power.mapel_batched`` call at finalization (a MAPEL solve per
    candidate subset — the literal paper procedure — is O(C(pool,K)) solves
    per round and only reorders near-ties). literal_graph_schedule keeps
    the paper's exact per-vertex power allocation."""
    power_fn = make_power_fn(power_mode, pmax, noise_power)
    rounds = _lazy_gwmin_rounds(
        gains_tm, weights_m, k, pmax=pmax, noise_power=noise_power,
        candidate_pool=candidate_pool, backend=backend, scorer=scorer,
        shards=shards,
    )
    return finalize_schedule(
        rounds, gains_tm, weights_m, power_fn, noise_power, "lazy-gwmin"
    )


def _lazy_gwmin_rounds(
    gains_tm, weights_m, k, *, pmax, noise_power, candidate_pool, backend,
    scorer="xla", shards=None,
):
    """Selection step of the lazy greedy (the subset *search* runs at max
    power regardless of the finalization power mode — see
    ``lazy_greedy_schedule``)."""
    search_fn = make_power_fn("max", pmax, noise_power)
    if backend == "numpy":
        return _greedy_rounds_numpy(
            gains_tm, weights_m, k, search_fn, noise_power, candidate_pool, pmax
        )
    if backend == "jax":
        return _greedy_rounds_jax_fused(
            gains_tm, weights_m, k, search_fn, noise_power, candidate_pool,
            pmax, scorer=scorer, shards=shards,
        )
    if backend == "jax-stepwise":
        return _greedy_rounds_jax_stepwise(
            gains_tm, weights_m, k, search_fn, noise_power, candidate_pool, pmax
        )
    raise ValueError(
        f"unknown scheduling backend {backend!r}; known: {SCHEDULER_BACKENDS}"
    )


# --------------------------------------------------------------------------
# Exact optimum (tests only)
# --------------------------------------------------------------------------

def brute_force_schedule(
    gains_tm, weights_m, k, *, power_mode="max", pmax=0.01, noise_power=1e-13
) -> Schedule:
    """Enumerate every feasible schedule (C1/C2) — exponential, tests only."""
    power_fn = make_power_fn(power_mode, pmax, noise_power)
    num_rounds, num_devices = gains_tm.shape
    subsets = list(itertools.combinations(range(num_devices), k))
    subs_vk = np.array(subsets, dtype=np.intp).reshape(len(subsets), k)
    vals = {
        (s, t): v
        for t in range(num_rounds)
        for s, v in zip(
            subsets,
            score_subsets(subs_vk, t, gains_tm, weights_m, power_fn, noise_power),
        )
    }
    best_total, best_assign = -np.inf, None

    def rec(t, used, total, assign):
        nonlocal best_total, best_assign
        if t == num_rounds:
            if total > best_total:
                best_total, best_assign = total, list(assign)
            return
        for s in subsets:
            if used & set(s):
                continue
            assign.append(s)
            rec(t + 1, used | set(s), total + vals[(s, t)], assign)
            assign.pop()

    rec(0, set(), 0.0, [])
    return finalize_schedule(
        best_assign, gains_tm, weights_m, power_fn, noise_power, "brute-force"
    )


# --------------------------------------------------------------------------
# Baseline schedulers (paper §IV comparisons and ref [6] policies)
# --------------------------------------------------------------------------

def _random_rounds(rng: np.random.Generator, num_rounds, num_devices, k):
    """Selection step of random scheduling: one device permutation, chunked
    into K-groups round by round (tail rounds past the supply come back
    empty)."""
    perm = rng.permutation(num_devices)
    return [tuple(perm[t * k : (t + 1) * k].tolist()) for t in range(num_rounds)]


def random_schedule(
    rng: np.random.Generator, gains_tm, weights_m, k,
    *, power_mode="max", pmax=0.01, noise_power=1e-13,
) -> Schedule:
    """Random scheduling respecting C1 (each device at most once)."""
    power_fn = make_power_fn(power_mode, pmax, noise_power)
    num_rounds, num_devices = gains_tm.shape
    rounds = _random_rounds(rng, num_rounds, num_devices, k)
    return finalize_schedule(
        rounds, gains_tm, weights_m, power_fn, noise_power, "random"
    )


def _round_robin_rounds(num_rounds, num_devices, k):
    """Selection step of round robin: fixed device order, K per round."""
    return [
        tuple(range(min(t * k, num_devices), min((t + 1) * k, num_devices)))
        for t in range(num_rounds)
    ]


def round_robin_schedule(
    gains_tm, weights_m, k, *, power_mode="max", pmax=0.01, noise_power=1e-13
) -> Schedule:
    """Round robin: fixed device order, K per round (ref [6] policy).

    When T*K > M the tail rounds get the leftover devices (possibly none)
    instead of emitting out-of-range device ids — C1 still holds and every
    id stays < num_devices.
    """
    power_fn = make_power_fn(power_mode, pmax, noise_power)
    num_rounds, num_devices = gains_tm.shape
    rounds = _round_robin_rounds(num_rounds, num_devices, k)
    return finalize_schedule(
        rounds, gains_tm, weights_m, power_fn, noise_power, "round-robin"
    )


def _proportional_fair_rounds(
    gains_tm, weights_m, k, *, by_gain, pmax, noise_power
):
    """Selection step of proportional fair: greedy top-K unused devices.

    Default ranking is the weighted solo-proxy rate w_k log2(1 + p g^2 /
    sigma^2) — the same per-device quantity the MWIS objective sums — with
    a stable sort so score ties keep the lower device id.  ``by_gain=True``
    reproduces the seed's raw-gain ranking (which ignored the FedAvg
    weights the objective weighs by) bit-for-bit, unstable sort included.
    """
    num_rounds, num_devices = gains_tm.shape
    used = set()
    rounds = []
    for t in range(num_rounds):
        avail = np.array(
            [d for d in range(num_devices) if d not in used], dtype=np.intp
        )
        if by_gain:
            order = avail[np.argsort(-gains_tm[t, avail])]
        else:
            score = _solo_proxy(
                gains_tm[t, avail], weights_m[avail], pmax, noise_power
            )
            order = avail[np.argsort(-score, kind="stable")]
        grp = tuple(order[:k].tolist())
        used |= set(grp)
        rounds.append(grp)
    return rounds


def proportional_fair_schedule(
    gains_tm, weights_m, k, *, power_mode="max", pmax=0.01, noise_power=1e-13,
    by_gain=False,
) -> Schedule:
    """Per round, pick the K best unused devices by weighted solo rate.

    The ranking is w_k R_k^solo (see ``_proportional_fair_rounds``) so this
    baseline competes on the objective the MWIS scheduler is scored against;
    the seed ranked by raw channel gain, which starves high-weight /
    mid-gain devices — pass ``by_gain=True`` to reproduce that behaviour.

    When every device has been used before the horizon ends (T*K > M) the
    remaining rounds get empty groups, like round-robin's tail — the intp
    dtype keeps the empty-``avail`` gather legal (a bare ``np.array([])`` is
    float64 and rejects fancy indexing).
    """
    power_fn = make_power_fn(power_mode, pmax, noise_power)
    rounds = _proportional_fair_rounds(
        gains_tm, weights_m, k, by_gain=by_gain, pmax=pmax,
        noise_power=noise_power,
    )
    return finalize_schedule(
        rounds, gains_tm, weights_m, power_fn, noise_power, "proportional-fair"
    )


# --------------------------------------------------------------------------
# SchedulerPolicy protocol, registry, and the registered policies
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PolicyConfig:
    """Everything a policy may read at ``init_state`` time.

    The FL runtime builds this from ``FLConfig`` + the cell physics
    (``fl.policy_config``); standalone callers construct it directly.
    ``seed`` seeds any policy-internal randomness — schedules must be
    reproducible from (inputs, PolicyConfig) alone.
    """

    group_size: int                 # K
    power_mode: str = "max"         # finalization allocator (max | mapel)
    pmax: float = 0.01
    noise_power: float = 1e-13
    candidate_pool: int = 24        # lazy greedy enumeration bound
    backend: str = "numpy"          # lazy greedy driver (SCHEDULER_BACKENDS)
    scorer: str = "xla"             # fused-backend vertex scorer (xla | pallas)
    shards: "int | None" = None     # fused-backend vertex-axis device shards
    ota_noise: float = 0.0          # OTA receiver noise std (matching-pursuit
                                    # aggregation-error model; 0 = noiseless)
    seed: int = 0


@dataclasses.dataclass
class Observation:
    """Online observables fed to ``select_round`` (all (M,) arrays).

    The FL runtime updates these after every live round
    (:meth:`record_round`); offline drivers (:func:`build_schedule`) feed
    realized rates and participation but no update norms (there is no FL
    state outside the training loop).
    """

    update_norms: np.ndarray    # last observed ||delta W_k||_2; 0 if never
    participation: np.ndarray   # rounds device k was scheduled so far
    last_round: np.ndarray      # last round k participated; -1 if never
    realized_rates: np.ndarray  # rate k achieved when last scheduled; 0 if never

    @classmethod
    def initial(cls, num_devices: int) -> "Observation":
        return cls(
            update_norms=np.zeros(num_devices),
            participation=np.zeros(num_devices, dtype=np.intp),
            last_round=np.full(num_devices, -1, dtype=np.intp),
            realized_rates=np.zeros(num_devices),
        )

    def record_round(self, t, group, rates_k, update_norms_k=None) -> "Observation":
        """Functional update after round t (the caller keeps the new copy,
        so a policy holding an old Observation never sees the future)."""
        obs = Observation(
            self.update_norms.copy(), self.participation.copy(),
            self.last_round.copy(), self.realized_rates.copy(),
        )
        idx = np.asarray(group, dtype=np.intp)
        if idx.size:
            obs.participation[idx] += 1
            obs.last_round[idx] = t
            obs.realized_rates[idx] = np.asarray(rates_k, dtype=np.float64)
            if update_norms_k is not None:
                obs.update_norms[idx] = np.asarray(update_norms_k, dtype=np.float64)
        return obs


class TracedObservation(NamedTuple):
    """The jnp mirror of :class:`Observation`, threaded through the
    scanned-horizon ``lax.scan`` carry (``fl_engine._online_horizon_core``).

    A NamedTuple of arrays, so it is a pytree the scan can carry.
    ``realized_rates`` is omitted on purpose: no registered traced policy
    reads it (the scores consume the *solo* rate proxy, not the realized
    SIC rate), and dropping it keeps the carry minimal — add it here (and
    in the engine's scatter update) if a future policy needs it.
    """

    update_norms: Any    # (M,) f32 last observed ||delta W_k||; the carry
                         # seeds these at the policy's COLD_START_NORM
    participation: Any   # (M,) i32 rounds device k was scheduled so far
    last_round: Any      # (M,) i32 last round k participated; -1 if never

    @classmethod
    def initial(cls, num_devices: int,
                cold_start_norm: float = 1.0) -> "TracedObservation":
        import jax.numpy as jnp

        return cls(
            update_norms=jnp.full(num_devices, cold_start_norm, jnp.float32),
            participation=jnp.zeros(num_devices, jnp.int32),
            last_round=jnp.full(num_devices, -1, jnp.int32),
        )


def _norm_estimates_traced(obs: "TracedObservation", cold_start: float):
    """jnp mirror of the shared numpy norm-estimate convention
    (``UpdateAwarePolicy._score`` / ``MatchingPursuitPolicy._norm_estimates``):
    devices never yet observed take the running mean of observed norms
    (``cold_start`` before any observation) and observed-zero norms are
    floored at 1e-3 of the default, so no device is starved forever."""
    import jax.numpy as jnp

    seen = obs.participation > 0
    cnt = jnp.sum(seen.astype(jnp.float32))
    total = jnp.sum(jnp.where(seen, obs.update_norms, 0.0))
    default = jnp.where(
        cnt > 0.0, total / jnp.maximum(cnt, 1.0), jnp.float32(cold_start)
    )
    default = jnp.maximum(default, 1e-12)
    return jnp.where(
        seen, jnp.maximum(obs.update_norms, 1e-3 * default), default
    )


class SchedulerPolicy(Protocol):
    """The scheduling policy protocol (see module docstring).

    ``online`` declares whether ``select_round`` reads FL state from the
    Observation (live mode inside the training loop) or replays a
    precomputed plan; ``respects_c1`` whether the policy schedules each
    device at most once over the horizon (the paper's C1).  Online
    policies may additionally declare ``needs_norms`` (default True) —
    set it False to tell the FL loop not to compute per-device update
    norms the policy never reads.

    Online policies opting into the scanned horizon implement the traced
    protocol on top (``traced_protocol = True``):

        aux = policy.init_traced(gains_tm, weights_m, cfg)   # host, once
        dev_k, mask_k = policy.select_round_traced(
            t, solo_m, gains_m, weights_m, obs, cfg)         # traced

    ``init_traced`` returns host numpy float32 aux tensors (currently the
    (T, M) weighted solo-rate table, computed in float64 and cast once);
    ``select_round_traced`` receives that table's round-t row plus the
    round's jnp channel row and a :class:`TracedObservation`, and returns
    a fixed-shape (K,) int32 device vector with a (K,) bool validity mask
    (lanes masked False are padding — the engine drops their scatter
    updates and zeroes their aggregation weights).
    """

    name: str
    online: bool
    respects_c1: bool

    def init_state(self, gains_tm: np.ndarray, weights_m: np.ndarray,
                   cfg: PolicyConfig) -> Any: ...

    def select_round(self, t: int, state: Any,
                     obs: Observation) -> "tuple[tuple, Any]": ...


_REGISTRY: "dict[str, type]" = {}


def register_policy(name: str):
    """Class decorator registering a SchedulerPolicy under ``name``.

    The name immediately becomes a valid ``FLConfig.scheduler`` value
    (config validation reads :func:`available_policies`).
    """

    def deco(cls):
        if name in _REGISTRY:
            raise ValueError(f"policy {name!r} already registered")
        cls.name = name
        _REGISTRY[name] = cls
        return cls

    return deco


def get_policy(name: str, **options) -> "SchedulerPolicy":
    """Instantiate the policy registered under ``name``.

    ``options`` are forwarded to the policy constructor (e.g.
    ``get_policy("proportional-fair", by_gain=True)``).
    """
    try:
        cls = _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown scheduler {name!r}; registered: {available_policies()}"
        ) from None
    return cls(**options)


def available_policies() -> tuple:
    """Sorted names of all registered policies."""
    return tuple(sorted(_REGISTRY))


def policy_is_online(name: str) -> bool:
    """Whether the policy registered under ``name`` selects from live FL
    state (``online = True``).

    Half of the horizon-mode gate: online policies need FL-state feedback
    every round, so under ``FLConfig.horizon = "scan"`` they must carry
    that feedback *inside* the device program via the traced protocol
    (:func:`policy_is_traced`) — config validation and the scanned driver
    ask these two questions together.  Raises ValueError for unregistered
    names (same as :func:`get_policy`).
    """
    return bool(getattr(get_policy(name), "online", False))


def policy_is_traced(name: str) -> bool:
    """Whether the policy registered under ``name`` implements the traced
    selection protocol (``traced_protocol = True`` + ``init_traced`` /
    ``select_round_traced`` — see :class:`SchedulerPolicy`).

    The other half of the horizon-mode gate: an *online* policy runs under
    ``FLConfig.horizon = "scan"`` iff this is True (its selection loop
    then executes inside ``fl_engine._online_horizon_core``'s scan body).
    Raises ValueError for unregistered names (same as :func:`get_policy`).
    """
    return bool(getattr(get_policy(name), "traced_protocol", False))


def build_schedule(
    policy: "SchedulerPolicy", gains_tm, weights_m, cfg: PolicyConfig
) -> Schedule:
    """Drive any policy over the whole horizon and finalize the result.

    Precomputed policies run their one-shot plan in ``init_state`` and this
    reduces to plan + shared finalization — bit-identical to the historical
    per-scheduler functions.  Online policies are driven with realized
    rates and participation fed back between rounds, but no update norms
    (FL state exists only inside ``fl.run_federated_learning``'s live
    mode); useful for rate-only studies and benchmarks.
    """
    gains_tm = np.asarray(gains_tm)
    weights_m = np.asarray(weights_m)
    num_rounds, num_devices = gains_tm.shape
    power_fn = power_lib.make_power_allocator(
        cfg.power_mode, cfg.pmax, cfg.noise_power
    )
    state = policy.init_state(gains_tm, weights_m, cfg)
    obs = Observation.initial(num_devices)
    online = getattr(policy, "online", False)
    rounds, powers, rates, total = [], [], [], 0.0
    for t in range(num_rounds):
        group, state = policy.select_round(t, state, obs)
        group = tuple(int(d) for d in group)
        rounds.append(group)
        if online:
            # the loop must allocate per round anyway (the policy reads the
            # realized rates next round), so keep the results instead of
            # re-solving every group in a trailing finalize_schedule pass
            p_k, r_k = finalize_round(
                group, t, gains_tm, weights_m, power_fn, cfg.noise_power
            )
            obs = obs.record_round(t, group, r_k)
            powers.append(p_k)
            rates.append(r_k)
            total += float(np.sum(weights_m[np.asarray(group, np.intp)] * r_k))
    revisits = not getattr(policy, "respects_c1", True)
    if online:
        sched = Schedule(rounds, powers, rates, total, policy.name, revisits)
    else:
        sched = finalize_schedule(
            rounds, gains_tm, weights_m, power_fn, cfg.noise_power, policy.name
        )
        sched.allow_revisits = revisits
    sched.validate(num_devices, cfg.group_size)
    return sched


class _PrecomputedPolicy:
    """Base for offline policies: plan the whole horizon in ``init_state``
    (selection depends only on channel realizations), replay per round."""

    online = False
    respects_c1 = True

    def init_state(self, gains_tm, weights_m, cfg: PolicyConfig):
        return self._plan(np.asarray(gains_tm), np.asarray(weights_m), cfg)

    def select_round(self, t, state, obs):
        return tuple(state[t]), state


@register_policy("lazy-gwmin")
class LazyGwminPolicy(_PrecomputedPolicy):
    """Graph-free Algorithm 2 (the paper's proposed MWIS scheduler)."""

    def _plan(self, gains_tm, weights_m, cfg):
        return _lazy_gwmin_rounds(
            gains_tm, weights_m, cfg.group_size, pmax=cfg.pmax,
            noise_power=cfg.noise_power, candidate_pool=cfg.candidate_pool,
            backend=cfg.backend, scorer=cfg.scorer, shards=cfg.shards,
        )


@register_policy("literal-gwmin")
class LiteralGwminPolicy(_PrecomputedPolicy):
    """Algorithm 2 on the explicit scheduling graph (small M only)."""

    def _plan(self, gains_tm, weights_m, cfg):
        power_fn = power_lib.make_power_allocator(
            cfg.power_mode, cfg.pmax, cfg.noise_power
        )
        return _literal_gwmin_rounds(
            gains_tm, weights_m, cfg.group_size, power_fn, cfg.noise_power
        )


@register_policy("random")
class RandomPolicy(_PrecomputedPolicy):
    """Random C1-respecting schedule, reproducible from the policy alone.

    The RNG is derived in ``init_state`` from ``cfg.seed + SEED_OFFSET``;
    the offset decorrelates the scheduling permutation from the model-init /
    channel streams that consume ``cfg.seed`` directly.  (Historically the
    ``+ 17`` lived as a magic number inside ``fl.make_schedule``.)
    """

    SEED_OFFSET = 17

    def _plan(self, gains_tm, weights_m, cfg):
        rng = np.random.default_rng(cfg.seed + self.SEED_OFFSET)
        num_rounds, num_devices = gains_tm.shape
        return _random_rounds(rng, num_rounds, num_devices, cfg.group_size)


@register_policy("round-robin")
class RoundRobinPolicy(_PrecomputedPolicy):
    """Fixed device order, K per round (ref [6] baseline)."""

    def _plan(self, gains_tm, weights_m, cfg):
        num_rounds, num_devices = gains_tm.shape
        return _round_robin_rounds(num_rounds, num_devices, cfg.group_size)


@register_policy("proportional-fair")
class ProportionalFairPolicy(_PrecomputedPolicy):
    """Greedy top-K unused devices by weighted solo rate (``by_gain=True``
    reproduces the seed's raw-gain ranking)."""

    def __init__(self, by_gain: bool = False):
        self.by_gain = by_gain

    def _plan(self, gains_tm, weights_m, cfg):
        return _proportional_fair_rounds(
            gains_tm, weights_m, cfg.group_size, by_gain=self.by_gain,
            pmax=cfg.pmax, noise_power=cfg.noise_power,
        )


class _ScoreTopKPolicy:
    """Base for online policies: rank all devices by a per-round score and
    take the top K (stable sort, ties to the lower device id).  Subclasses
    implement ``_score(t, solo, obs) -> (M,)`` where ``solo`` is the
    weighted interference-free rate w_k log2(1 + p g_k^2 / sigma^2) at
    round t.  Online policies revisit devices across rounds — long-horizon
    fairness is the score's job, not C1's.
    """

    online = True
    respects_c1 = False
    needs_norms = False     # True: the FL loop computes ||delta W_k|| per
                            # scheduled device and feeds it back via obs
    traced_protocol = True  # subclasses supply _score_traced, the jnp
                            # mirror of _score (same ranking, f32)

    def init_state(self, gains_tm, weights_m, cfg: PolicyConfig):
        return {
            "gains": np.asarray(gains_tm),
            "weights": np.asarray(weights_m),
            "cfg": cfg,
        }

    def select_round(self, t, state, obs):
        cfg = state["cfg"]
        solo = _solo_proxy(
            state["gains"][t], state["weights"], cfg.pmax, cfg.noise_power
        )
        score = np.asarray(self._score(t, solo, obs), dtype=np.float64)
        k = min(cfg.group_size, len(score))
        top = np.argsort(-score, kind="stable")[:k]
        return tuple(int(d) for d in top), state

    def init_traced(self, gains_tm, weights_m, cfg: PolicyConfig) -> dict:
        """Host aux for the traced path: the (T, M) weighted solo-rate
        table, computed in float64 (exactly what ``select_round`` sees)
        and cast once to the program's float32."""
        solo = _solo_proxy(
            np.asarray(gains_tm, np.float64),
            np.asarray(weights_m, np.float64),
            cfg.pmax, cfg.noise_power,
        )
        return {"solo": np.asarray(solo, np.float32)}

    def select_round_traced(self, t, solo_m, gains_m, weights_m, obs, cfg):
        """jnp mirror of ``select_round``: top-K of ``_score_traced`` via
        ``lax.top_k`` (ties to the lower device id, matching the stable
        descending argsort).  Top-K policies always fill all K lanes, so
        the validity mask is all-True."""
        import jax
        import jax.numpy as jnp

        score = self._score_traced(t, solo_m, obs)
        k = min(int(cfg.group_size), int(score.shape[0]))
        _, top = jax.lax.top_k(score, k)
        return top.astype(jnp.int32), jnp.ones(k, dtype=bool)


@register_policy("update-aware")
class UpdateAwarePolicy(_ScoreTopKPolicy):
    """Update-aware scheduling (Amiri et al., arXiv:2001.10402).

    Score = (estimated ||delta W_k||_2) * (weighted solo rate): devices
    whose recent local updates were large *and* whose uplink is currently
    fast win the slot — the BN2-BC flavour of the reference, with the last
    observed norm standing in for the (untransmitted) current one.  Devices
    never yet observed take the running mean of observed norms (1.0 before
    any observation), so round 0 reduces to best-channel and unexplored
    devices stay competitive; observed-zero norms are floored so a device
    whose local gradient once came back numerically zero (the norm is
    taken on the raw pre-quantization delta) is merely deprioritized, not
    starved forever.
    """

    needs_norms = True
    COLD_START_NORM = 1.0   # the documented cold-start estimate: stands in
                            # for ||delta W_k|| before any observation, so
                            # round 0 reduces to best-channel; the traced
                            # carry seeds its norms with it too

    def _score(self, t, solo, obs):
        norms = obs.update_norms.copy()
        seen = obs.participation > 0
        default = (
            float(norms[seen].mean()) if seen.any() else self.COLD_START_NORM
        )
        default = max(default, 1e-12)
        norms[~seen] = default
        norms[seen] = np.maximum(norms[seen], 1e-3 * default)
        return norms * solo

    def _score_traced(self, t, solo_m, obs):
        return _norm_estimates_traced(obs, self.COLD_START_NORM) * solo_m


@register_policy("age-fair")
class AgeFairPolicy(_ScoreTopKPolicy):
    """Age-fair scheduling (Yang et al., arXiv:1908.06287).

    Score = (1 + age_k) * (weighted solo rate), age_k = rounds since device
    k last participated (never-scheduled devices age from round 0).  The
    staleness boost grows without bound, so every device is eventually
    rescheduled no matter how weak its channel — the update-age fairness
    the reference shows FL needs over long horizons.
    """

    def _score(self, t, solo, obs):
        age = (t - obs.last_round).astype(np.float64)
        return (1.0 + age) * solo

    def _score_traced(self, t, solo_m, obs):
        import jax.numpy as jnp

        age = (t - obs.last_round).astype(jnp.float32)
        return (1.0 + age) * solo_m


@register_policy("matching-pursuit")
class MatchingPursuitPolicy:
    """Greedy residual-error device selection for over-the-air aggregation.

    The analog PS estimate (core/ota.py) misses the updates of unscheduled
    devices and pays receiver noise amplified by the weakest admitted
    channel (truncated inversion: eta <= pmax h_k^2 / (w_k n_k)^2 for every
    admitted k).  Modeling the round's aggregation error of a candidate set
    S as

        E(S) = sum_{k not in S} (w_k n_k)^2
             + lambda * max_{k in S} (w_k n_k / h_k)^2,
        lambda = ota_noise^2 / pmax,

    the policy runs a matching-pursuit sweep: start from S = {} (error =
    total update energy), repeatedly admit the device giving the largest
    *strict* decrease of E, and stop at K devices or when no admission
    helps — a weak-channel device whose noise penalty outweighs its energy
    contribution is left out even when slots remain.  With ``ota_noise = 0``
    the noise term vanishes and the sweep reduces to top-K by w_k n_k.

    Norm estimates follow ``update-aware``'s convention: devices never yet
    observed take the running mean of observed norms (1.0 before any
    observation) and observed-zero norms are floored, so round 0 is a pure
    channel/weight ranking and no device is starved forever.
    """

    online = True
    respects_c1 = False
    needs_norms = True
    traced_protocol = True
    COLD_START_NORM = 1.0   # shared with update-aware: the documented
                            # stand-in norm before any observation

    def init_state(self, gains_tm, weights_m, cfg: PolicyConfig):
        return {
            "gains": np.asarray(gains_tm),
            "weights": np.asarray(weights_m),
            "cfg": cfg,
        }

    @classmethod
    def _norm_estimates(cls, obs: Observation) -> np.ndarray:
        norms = obs.update_norms.copy()
        seen = obs.participation > 0
        default = (
            float(norms[seen].mean()) if seen.any() else cls.COLD_START_NORM
        )
        default = max(default, 1e-12)
        norms[~seen] = default
        norms[seen] = np.maximum(norms[seen], 1e-3 * default)
        return norms

    def select_round(self, t, state, obs):
        cfg = state["cfg"]
        gains = np.asarray(state["gains"][t], dtype=np.float64)
        weights = np.asarray(state["weights"], dtype=np.float64)
        m = weights * self._norm_estimates(obs)        # w_k n_k
        energy = m * m                                 # omission cost
        lam = float(cfg.ota_noise) ** 2 / max(float(cfg.pmax), 1e-300)
        if lam > 0.0:
            with np.errstate(divide="ignore"):
                pen = lam * np.where(gains > 0.0, (m / gains) ** 2, np.inf)
        else:
            pen = np.zeros_like(m)     # explicit: avoids 0 * inf = nan
        k = min(cfg.group_size, len(m))
        selected: "list[int]" = []
        in_s = np.zeros(len(m), dtype=bool)
        residual = float(energy.sum())     # sum over k not in S
        noise_term = 0.0                   # lambda * max admitted penalty
        cur = residual + noise_term
        for _ in range(k):
            cand_noise = np.maximum(noise_term, pen)
            e = (residual - energy) + cand_noise
            e[in_s] = np.inf
            j = int(np.argmin(e))
            if not e[j] < cur:     # admit only on strict decrease
                break
            selected.append(j)
            in_s[j] = True
            residual -= float(energy[j])
            noise_term = max(noise_term, float(pen[j]))
            cur = float(e[j])
        return tuple(selected), state

    def init_traced(self, gains_tm, weights_m, cfg: PolicyConfig) -> dict:
        """Same aux contract as the top-K policies (the engine feeds every
        traced policy the solo table); the admit loop itself only reads
        the channel row, the weights and the norm estimates."""
        solo = _solo_proxy(
            np.asarray(gains_tm, np.float64),
            np.asarray(weights_m, np.float64),
            cfg.pmax, cfg.noise_power,
        )
        return {"solo": np.asarray(solo, np.float32)}

    def select_round_traced(self, t, solo_m, gains_m, weights_m, obs, cfg):
        """The matching-pursuit sweep as a ``lax.while_loop``: one admit
        per iteration, stopping at K admissions or the first candidate
        that fails the strict-decrease test — the same early exit as the
        numpy loop, so both paths admit identical devices in identical
        order.  Lanes past the stop count are padding (mask False)."""
        import jax
        import jax.numpy as jnp

        m_arr = weights_m * _norm_estimates_traced(obs, self.COLD_START_NORM)
        energy = m_arr * m_arr
        lam = float(cfg.ota_noise) ** 2 / max(float(cfg.pmax), 1e-300)
        if lam > 0.0:
            safe_g = jnp.where(gains_m > 0.0, gains_m, 1.0)
            pen = jnp.where(
                gains_m > 0.0, lam * (m_arr / safe_g) ** 2, jnp.inf
            )
        else:
            pen = jnp.zeros_like(m_arr)   # explicit: avoids 0 * inf = nan
        k = min(int(cfg.group_size), int(m_arr.shape[0]))
        inf = jnp.asarray(jnp.inf, m_arr.dtype)

        def cond(c):
            cnt, _, _, _, _, _, stop = c
            return jnp.logical_and(cnt < k, jnp.logical_not(stop))

        def step(c):
            cnt, in_s, residual, noise_term, cur, sel, _ = c
            cand_noise = jnp.maximum(noise_term, pen)
            e = jnp.where(in_s, inf, (residual - energy) + cand_noise)
            j = jnp.argmin(e)              # first occurrence, like numpy
            admit = e[j] < cur             # strict decrease only
            sel = sel.at[cnt].set(jnp.where(admit, j.astype(jnp.int32), 0))
            in_s = in_s.at[j].set(jnp.logical_or(in_s[j], admit))
            return (
                cnt + jnp.where(admit, 1, 0).astype(jnp.int32),
                in_s,
                jnp.where(admit, residual - energy[j], residual),
                jnp.where(admit, jnp.maximum(noise_term, pen[j]), noise_term),
                jnp.where(admit, e[j], cur),
                sel,
                jnp.logical_not(admit),
            )

        total = jnp.sum(energy)
        c0 = (
            jnp.zeros((), jnp.int32),
            jnp.zeros(m_arr.shape[0], dtype=bool),
            total,
            jnp.zeros((), m_arr.dtype),
            total,
            jnp.zeros(k, jnp.int32),
            jnp.asarray(False),
        )
        cnt, _, _, _, _, sel, _ = jax.lax.while_loop(cond, step, c0)
        return sel, jnp.arange(k, dtype=jnp.int32) < cnt
