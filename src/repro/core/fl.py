"""Federated learning runtime (paper Algorithm 1 + §IV simulation).

Faithful paper-scale FedAvg over the simulated NOMA cell:
  per round t:
    1. PS broadcasts theta^t (downlink timing model, no compression).
    2. The scheduler assigns K devices to round t.  Precomputed policies
       (MWIS schedule over the whole horizon, the §IV baselines) planned
       this before training started; online policies (``policy.online``,
       e.g. update-aware / age-fair) are called *here*, inside the loop,
       reading the previous rounds' update norms, participation counts,
       and realized rates from a ``scheduling.Observation``.
    3. Each scheduled device runs local SGD on its own non-iid shard and
       produces a model delta.
    4. The uplink rate of each device sets the bit budget c_k = R_k * B * t;
       the delta is DoReFa-quantized to b_k = floor(32 / r_k) bits (paper
       §II-B).  Under NOMA that is the SIC rate over the shared slot; under
       TDMA each device gets its interference-free rate over its own
       sub-slot (adaptive compression applies to both uplinks — comparing a
       compressed NOMA run against an uncompressed TDMA run would bias the
       Fig. 5 comparison).
    5. PS aggregates: theta^{t+1} = theta^t + sum_k w_k * dq(delta_k),
       w_k = |D_k| / sum_selected |D_k|: weighted FedAvg, the usual
       reading of the aggregate in the paper's Algorithm 1 (line 10), which
       writes the sum over the scheduled devices without its weights.
  Timing: NOMA round = t_slot + T_d; TDMA round = K * t_slot + T_d (§IV).

Two round-body engines implement steps 3-5, selected by
``FLConfig.fl_engine`` (this module owns the driver — scheduling, power,
budgets, timing, and logging are computed once and shared by both):

  * ``"legacy"`` — :func:`_legacy_round`: one host-level ``local_update``
    per scheduled device (K shard uploads + K jitted scans + K eager
    quantize passes + host ``tree_map`` aggregation per round).  Simple,
    transparent, and kept as the **oracle** the batched engine is pinned
    against (``tests/test_fl_engine.py``).
  * ``"batched"`` — :class:`repro.core.fl_engine.BatchedRoundEngine`: all
    M shards live on device in a ``ClientBank`` and the whole round body
    (K-row gather -> vmapped local SGD -> batched norms -> traced
    per-client adaptive quantization -> weighted aggregation) is **one
    jitted dispatch**.  Aggregation uses an XLA einsum by default or the
    fused dequant+aggregate Pallas kernel under ``FLConfig.use_pallas``.
    Same schedules, same bit-widths, accuracies equal to f32 tolerance;
    use it for large-M / large-K sweeps (BENCH_fl.json tracks the
    round-loop speedup).

The per-client SGD math itself lives in one place —
``fl_engine.sgd_epoch`` — which the legacy path jits per device and the
batched engine vmaps over the client axis.

The LLM-scale integration of the same compression lives in
repro/launch/train.py (quantized-DSGD inside the pjit'd step).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import FLConfig
from repro.core import channel as chan
from repro.core import compression, errors, fl_engine, noma, scheduling
from repro.core import ota as ota_lib
from repro.core import power as power_lib
from repro.core import quantization as qlib
from repro.data.client_bank import ClientBank, EvalBank, eval_sample_plan
from repro.models.fl_models import frozen_base, get_fl_model
from repro.utils import spans
from repro.utils.tree import tree_count


@dataclasses.dataclass
class RoundLog:
    round: int
    devices: tuple
    rates: np.ndarray            # spectral efficiency per scheduled device
    bits: np.ndarray             # quantization bit-widths used
    compression_ratios: np.ndarray
    test_accuracy: float
    wall_time_s: float           # cumulative simulated communication time


@dataclasses.dataclass
class FLResult:
    logs: list
    final_params: dict
    scheme: str

    def accuracies(self):
        return np.array([l.test_accuracy for l in self.logs])

    def times(self):
        return np.array([l.wall_time_s for l in self.logs])


# --------------------------------------------------------------------------
# Local training (the FLModel payload on device shards)
# --------------------------------------------------------------------------

# One jitted epoch per device — the same per-client math the batched engine
# vmaps; the single implementation lives in fl_engine.sgd_epoch (``unroll``
# is a scan parameter and ``model`` a hashable FLModel, hence static).
_sgd_epoch = jax.jit(fl_engine.sgd_epoch, static_argnames=("model", "unroll"))


def local_update(params, xs, ys, cfg: FLConfig, model, base=None):
    """Run local epochs; returns the model delta (new - old).

    Padding generalizes over the trailing feature/label shape: flat image
    rows with scalar labels, or (S,) token rows with (S,) shifted labels —
    pad positions always carry label -1, the shared validity convention.
    ``base`` is the payload's frozen base (``fl_engine.bound``).
    """
    n = len(xs)
    bs = cfg.batch_size
    n_batches = max(1, (n + bs - 1) // bs)
    pad = n_batches * bs - n
    xp = np.concatenate([xs, np.zeros((pad, *xs.shape[1:]), xs.dtype)])
    yp = np.concatenate([ys, np.full((pad, *ys.shape[1:]), -1, ys.dtype)])
    xb = jnp.asarray(xp.reshape(n_batches, bs, *xs.shape[1:]))
    yb = jnp.asarray(yp.reshape(n_batches, bs, *ys.shape[1:]))
    new = params
    for _ in range(cfg.local_epochs):
        new = _sgd_epoch(new, xb, yb, cfg.learning_rate, model=model,
                         base=base)
    return jax.tree_util.tree_map(lambda a, b: a - b, new, params)


def _legacy_round(
    params, devs, budgets, agg_w, dataset, shards, cfg: FLConfig, payload,
    *, need_norms: bool, model, ota=None, base=None,
):
    """The per-device host round body (steps 3-5), kept as the oracle.

    One ``local_update`` + quantize pass per scheduled device, host
    ``tree_map`` aggregation.  Returns ``(params, bits_used, ratios,
    norms)`` — the same contract as ``BatchedRoundEngine.run_round``,
    including its ``ota`` dict (gains/key/pmax): under the OTA uplink the
    per-device deltas go over the air unquantized and the host stacks them
    into the SAME shared aggregation operator the batched engine calls
    (:func:`repro.core.ota.superpose_tree`), so the three drivers apply
    bit-identical OTA aggregation math to a given delta stack.
    """
    deltas, bits_used, ratios, norms = [], [], [], []
    for j, d in enumerate(devs):
        idx = shards[d]
        delta = local_update(
            params, dataset.x_train[idx], dataset.y_train[idx], cfg, model,
            base,
        )
        if need_norms:
            # the policies' norm signal is the raw local update, taken
            # before quantization (Amiri et al. rank by what the device
            # computed, not by what the channel let through); policies
            # that never read obs.update_norms skip the per-device
            # reduction + host sync entirely
            norms.append(_tree_l2(delta))
        if cfg.compression == "adaptive":
            # NOMA: SIC rate over the shared slot; TDMA: interference-free
            # rate over the device's own sub-slot. Both budgets are in
            # ``budgets`` — quantizing only the NOMA uplink would bias
            # the Fig. 5 comparison in TDMA's favour.
            b = int(qlib.adaptive_bits(payload, budgets[j]))
            delta = compression.encode_decode_tree(
                delta, b, paper_exact=cfg.paper_exact_range
            )
            bits_used.append(b)
            ratios.append(float(qlib.compression_ratio(payload, budgets[j])))
        else:
            bits_used.append(32)
            ratios.append(1.0)
        deltas.append(delta)

    if deltas and ota is not None:
        # over-the-air: stack the host-loop deltas client-major and let the
        # shared superposition operator aggregate (FLConfig already forced
        # compression='none', so the deltas above are raw)
        stacked = jax.tree_util.tree_map(
            lambda *ds: jnp.stack([jnp.asarray(d) for d in ds]), *deltas
        )
        update = ota_lib.superpose_tree(
            stacked,
            jnp.asarray(np.asarray(ota["gains"]), jnp.float32),
            jnp.asarray(np.asarray(agg_w), jnp.float32),
            jnp.asarray(ota["key"]),
            pmax=float(ota["pmax"]), noise_std=float(cfg.ota_noise),
            threshold=float(cfg.ota_threshold),
            use_pallas=bool(cfg.use_pallas),
        )
        params = jax.tree_util.tree_map(lambda p, u: p + u, params, update)
    elif deltas:
        update = jax.tree_util.tree_map(
            lambda *ds: sum(w * d for w, d in zip(agg_w, ds)), *deltas
        )
        params = jax.tree_util.tree_map(lambda p, u: p + u, params, update)
    # else: empty round (T*K > M schedules legitimately produce empty
    # tail groups) — no uplink, no aggregation.
    return params, bits_used, ratios, norms


# --------------------------------------------------------------------------
# Scheduling front-end
# --------------------------------------------------------------------------

def policy_config(cell: chan.CellConfig, cfg: FLConfig) -> scheduling.PolicyConfig:
    """PolicyConfig from the FL settings + the cell physics."""
    return scheduling.PolicyConfig(
        group_size=cfg.group_size,
        power_mode=cfg.power_mode,
        pmax=cell.max_power_w,
        noise_power=cell.noise_power_w,
        backend=cfg.scheduler_backend,
        ota_noise=cfg.ota_noise,
        seed=cfg.seed,
    )


def make_schedule(
    gains_tm: np.ndarray,
    weights_m: np.ndarray,
    cell: chan.CellConfig,
    cfg: FLConfig,
    policy: "scheduling.SchedulerPolicy | None" = None,
) -> scheduling.Schedule:
    """One-shot schedule via the policy registry (string if/elif retired).

    ``policy`` lets a caller that already resolved ``cfg.scheduler`` (e.g.
    ``run_federated_learning``) reuse the instance.  For online policies
    this drives ``select_round`` with rate/participation feedback only (no
    FL state outside the training loop) — the live path in
    :func:`run_federated_learning` is the real deal.
    """
    if policy is None:
        policy = scheduling.get_policy(cfg.scheduler)
    return scheduling.build_schedule(
        policy, gains_tm, weights_m, policy_config(cell, cfg)
    )


def _round_physics(devs, powers_t, rates, t, gains, cell, uplink, dl_time):
    """Uplink rates, bit budgets, and wall time of one scheduled round.

    The single owner of the §IV timing/budget rules, shared by the
    per-round host loop and the scanned-horizon packer — the scan-vs-
    per-round equality of rates, budgets and times holds by construction.
    Returns ``(rates, budgets, round_time)``; ``rates``/``budgets`` are
    (len(devs),) float64.
    """
    if uplink == "tdma":
        # each device alone in its sub-slot, interference-free
        p = powers_t
        g = gains[t, list(devs)]
        rates = np.asarray(
            noma.tdma_rates(jnp.asarray(p), jnp.asarray(g), cell.noise_power_w)
        )
        slot = cell.slot_seconds  # each scheduled device gets a full slot
        budgets = rates * cell.bandwidth_hz * slot
        # airtime = one sub-slot per *scheduled* device: empty/partial
        # T*K > M tail rounds must not be charged the full K sub-slots
        # (that skewed the Fig. 5 time axis against TDMA tails)
        round_time = len(devs) * cell.slot_seconds + dl_time
    else:
        # noma and ota share this branch: both spend ONE shared uplink slot
        # per non-empty round (the analog superposition *is* a simultaneous
        # transmission — that shared-slot airtime is OTA's whole appeal).
        # The SIC rates/budgets are still logged for OTA runs as the
        # digital-equivalent capacity of the same slot (nothing downstream
        # quantizes to them: compression='none' is enforced).
        rates = np.asarray(rates)
        budgets = rates * cell.bandwidth_hz * cell.slot_seconds
        # the shared uplink slot is only spent when someone transmits —
        # empty T*K > M tail rounds cost downlink only (mirrors the TDMA
        # per-device sub-slot accounting above)
        uplink_time = cell.slot_seconds if devs else 0.0
        round_time = uplink_time + dl_time
    return rates, budgets, round_time


def _agg_weights(sizes, devs) -> np.ndarray:
    """FedAvg weights w_k = |D_k| / sum_selected |D_k| — one owner so both
    drivers (and both engines) aggregate with identical host-float64
    values."""
    raw_w = [sizes[d] for d in devs]
    return np.asarray(raw_w) / max(sum(raw_w), 1.0)


def _tree_l2(tree) -> float:
    """||tree||_2 over all leaves (the update-aware policies' norm signal).

    The squared dots accumulate on device; the single ``float()`` at the end
    is the only host sync (this runs per scheduled device per live round).
    """
    leaves = jax.tree_util.tree_leaves(tree)
    return float(jnp.sqrt(sum(jnp.vdot(leaf, leaf) for leaf in leaves)))


# --------------------------------------------------------------------------
# Main simulation
# --------------------------------------------------------------------------

def run_federated_learning(
    dataset,
    shards: list,
    cell: chan.CellConfig,
    cfg: FLConfig,
    *,
    uplink: Optional[str] = None,    # "noma" | "tdma" | "ota"; None = cfg.uplink
    schedule: Optional[scheduling.Schedule] = None,
    eval_every: int = 1,
    progress: Optional[Callable[[RoundLog], None]] = None,
) -> FLResult:
    """Simulate the full FL process; returns per-round logs.

    dataset: repro.data.mnist_like.Dataset; shards: per-device index lists.

    ``uplink`` defaults to ``cfg.uplink`` and an explicit argument
    overrides it (re-validated against the config combos either way —
    ``ota.check_uplink``).  Under ``"ota"`` the round's aggregate is the
    noisy analog superposition (core/ota.py) instead of the digital
    decode-and-average.

    ``cfg.horizon = "scan"`` delegates to :func:`run_horizon_scanned`
    (the whole horizon as one device program — precomputed schedules and
    traced-protocol online policies alike; config validation already
    rejected the online policies that cannot trace); this host loop is
    the per-round driver every scanned path is equality-pinned against.
    """
    uplink = cfg.uplink if uplink is None else uplink
    ota_lib.check_uplink(
        uplink, compression=cfg.compression, topk=cfg.topk,
        power_mode=cfg.power_mode,
    )
    if cfg.horizon == "scan":
        return run_horizon_scanned(
            dataset, shards, cell, cfg, uplink=uplink, schedule=schedule,
            eval_every=eval_every, progress=progress,
        )
    key = jax.random.PRNGKey(cfg.seed)
    model = get_fl_model(cfg.model)
    params = model.init(key)
    payload = tree_count(params) * 32  # I: full-precision payload bits
    base = frozen_base(model)

    sizes = np.array([len(s) for s in shards], dtype=np.float64)
    weights = sizes / sizes.sum()

    # Round-body engine: "batched" folds steps 3-5 into one jitted dispatch
    # per round over a device-resident ClientBank; None selects the legacy
    # per-device host loop (the oracle — see module docstring).
    engine = None
    if cfg.fl_engine == "batched":
        engine = fl_engine.BatchedRoundEngine(
            dataset, shards, cfg, payload, model=model
        )

    # channel realizations for the whole horizon
    dist = chan.sample_positions(jax.random.fold_in(key, 1), cell)
    gains = np.asarray(
        chan.sample_round_channels(jax.random.fold_in(key, 2), dist, cell,
                                   cfg.num_rounds)
    )

    # Scheduling: precomputed policies (and caller-supplied schedules) fix
    # the whole horizon now; online policies run live inside the round loop.
    policy = obs = policy_state = allocator = None
    if schedule is None:
        policy = scheduling.get_policy(cfg.scheduler)
        if getattr(policy, "online", False):
            pcfg = policy_config(cell, cfg)
            policy_state = policy.init_state(gains, weights, pcfg)
            obs = scheduling.Observation.initial(cell.num_devices)
            allocator = power_lib.make_power_allocator(
                cfg.power_mode, cell.max_power_w, cell.noise_power_w
            )
        else:
            # one owner for precomputed construction (validated inside
            # build_schedule with the policy's own C1 expectation),
            # reusing the instance resolved above
            schedule = make_schedule(gains, weights, cell, cfg, policy=policy)
            policy = None
    else:
        # Caller-supplied schedule: its own allow_revisits flag (set by
        # build_schedule from the producing policy, or by the caller for a
        # hand-rolled revisiting schedule) decides C1 strictness.
        schedule.validate(cell.num_devices, cfg.group_size)

    # Downlink broadcast time on the large-scale gain only: the paper's
    # Fig. 5 time scale (35 rounds in ~10-22 s) implies a fading-free
    # downlink; with per-round Rayleigh draws the worst faded user's T_d
    # dominates both schemes and masks the NOMA/TDMA uplink gap.
    dl_gains = chan.large_scale_gain(dist, cell)
    dl_time = float(chan.downlink_time_seconds(payload, dl_gains, cell))

    # OTA receiver-noise keys for the whole horizon — the same host
    # precompute the scanned driver packs, so the two drivers draw
    # bit-identical noise per round
    ota_keys = (
        ota_lib.horizon_keys(cfg.seed, cfg.num_rounds)
        if uplink == "ota" else None
    )

    if engine is None:   # the batched engine evaluates through its EvalBank
        x_test = jnp.asarray(dataset.x_test)
        y_test = jnp.asarray(dataset.y_test)
        # bound methods are fresh objects per attribute access, so
        # jax.jit(model.accuracy) here would recompile every run; the
        # engine's module-level jit (model as a static arg) caches properly
        acc_fn = functools.partial(fl_engine._eval_full, model=model,
                                   base=base)

    logs = []
    t_wall = 0.0
    for t in range(cfg.num_rounds):
        if policy is not None:   # live mode: select with FL-state feedback
            group, policy_state = policy.select_round(t, policy_state, obs)
            devs = tuple(int(d) for d in group)
            scheduling.validate_group(
                devs, cell.num_devices, cfg.group_size,
                label=f"round-{t} group from policy {policy.name!r}",
            )
            powers_t, rates = scheduling.finalize_round(
                devs, t, gains, weights, allocator, cell.noise_power_w
            )
        else:
            devs = schedule.rounds[t]
            powers_t = schedule.powers[t]
            rates = schedule.rates[t]  # spectral efficiency (bit/s/Hz)
        rates, budgets, round_time = _round_physics(
            devs, powers_t, rates, t, gains, cell, uplink, dl_time
        )
        agg_w = _agg_weights(sizes, devs)
        need_norms = policy is not None and getattr(policy, "needs_norms", True)
        ota_round = None
        if ota_keys is not None and devs:
            ota_round = dict(
                gains=gains[t, list(devs)], key=ota_keys[t],
                pmax=float(cell.max_power_w),
            )
        if engine is not None:
            params, bits_used, ratios, norms = engine.run_round(
                params, devs, budgets, agg_w, need_norms=need_norms,
                ota=ota_round,
            )
        else:
            params, bits_used, ratios, norms = _legacy_round(
                params, devs, budgets, agg_w, dataset, shards, cfg, payload,
                need_norms=need_norms, model=model, ota=ota_round, base=base,
            )
        # empty rounds (T*K > M schedules legitimately produce empty tail
        # groups) train/aggregate nothing; the wall clock still advances and
        # the round is still logged below.

        if policy is not None:
            # feed realized norms/rates back for the next select_round
            # (norms is empty when the policy declared needs_norms=False)
            obs = obs.record_round(t, devs, np.asarray(rates),
                                   norms if norms else None)

        t_wall += round_time
        # the final round is always evaluated: accuracies()[-1] must measure
        # the final model even when eval_every skips over num_rounds - 1
        do_eval = t % eval_every == 0 or t == cfg.num_rounds - 1
        if not do_eval:
            acc = logs[-1].test_accuracy
        elif engine is not None:
            # batched engine: eval through the EvalBank gather (sampled per
            # cfg.eval_sample; at 1.0 bit-identical to the legacy full eval)
            acc = engine.evaluate(params, t)
        else:
            acc = float(acc_fn(params, x_test, y_test))
        log = RoundLog(t, tuple(devs), np.asarray(rates), np.asarray(bits_used),
                       np.asarray(ratios), acc, t_wall)
        logs.append(log)
        if progress:
            progress(log)

    scheme = f"{uplink}/{cfg.scheduler}/{cfg.power_mode}/{cfg.compression}"
    return FLResult(logs, params, scheme)


# --------------------------------------------------------------------------
# Scanned horizons: the whole precomputed simulation as ONE device program
# --------------------------------------------------------------------------

def _spanned(name: str):
    """Run the decorated host function inside the span ``name``
    (``repro.utils.spans``; README "Tracing a horizon" lists them)."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with spans.span(name):
                return fn(*args, **kwargs)
        return wrapped
    return deco


@dataclasses.dataclass
class _HorizonPlan:
    """Host-precomputed plan for one simulation instance (one seed).

    Everything the per-round driver computes on the host — model init,
    channel draws, schedule, rates, budgets, FedAvg weights, timing —
    packed into fixed-shape (T, K) tensors the scan consumes (zero-padded
    past each round's true group size; zero agg weights multiply the
    padding out of the aggregate exactly).
    """

    params0: dict                # freshly initialized model
    payload: int                 # I: full-precision payload bits
    schedule: scheduling.Schedule
    dev_tk: np.ndarray           # (T, K) int32 device ids, 0-padded
    ksizes: np.ndarray           # (T,) true per-round group sizes
    budgets_tk: np.ndarray       # (T, K) float64 uplink bit budgets, 0-padded
    aggw_tk: np.ndarray          # (T, K) float64 FedAvg weights, 0-padded
    gains_tk: np.ndarray         # (T, K) float32 channel amplitudes, 0-padded
                                 # (consumed only under the OTA uplink)
    noise_keys: np.ndarray       # (T, 2) uint32 OTA receiver-noise keys
    rates: list                  # per-round (k,) float64 uplink rates
    times: np.ndarray            # (T,) cumulative simulated wall clock
    eval_idx: "np.ndarray | None"  # (T, n) eval sample plan; None = full set


@_spanned("fl.plan")
def _horizon_setup(dataset, shards, cell, cfg: FLConfig, uplink, schedule):
    """Host precompute for one scanned instance.

    Mirrors :func:`run_federated_learning`'s setup exactly — same PRNG
    folds, same schedule construction, same :func:`_round_physics` /
    :func:`_agg_weights` calls — so the two drivers simulate the identical
    system and the equality grid can demand identical schedules, budgets,
    rates and times.
    """
    key = jax.random.PRNGKey(cfg.seed)
    params = get_fl_model(cfg.model).init(key)
    payload = tree_count(params) * 32

    sizes = np.array([len(s) for s in shards], dtype=np.float64)
    weights = sizes / sizes.sum()

    dist = chan.sample_positions(jax.random.fold_in(key, 1), cell)
    gains = np.asarray(
        chan.sample_round_channels(jax.random.fold_in(key, 2), dist, cell,
                                   cfg.num_rounds)
    )

    if schedule is None:
        policy = scheduling.get_policy(cfg.scheduler)
        if getattr(policy, "online", False):
            # Traced-protocol online policies are routed to the online
            # driver before this setup runs (run_horizon_scanned); any
            # online policy reaching a *precomputed* setup lacks that
            # protocol — guard direct calls with the pinned message
            # FLConfig raises at construction.
            raise ValueError(
                errors.ERR_SCAN_ONLINE_POLICY.format(scheduler=cfg.scheduler)
            )
        with spans.span("fl.schedule"):
            schedule = make_schedule(gains, weights, cell, cfg, policy=policy)
    else:
        schedule.validate(cell.num_devices, cfg.group_size)

    dl_gains = chan.large_scale_gain(dist, cell)
    dl_time = float(chan.downlink_time_seconds(payload, dl_gains, cell))

    T, K = cfg.num_rounds, cfg.group_size
    dev_tk = np.zeros((T, K), np.int32)
    ksizes = np.zeros(T, np.intp)
    budgets_tk = np.zeros((T, K), np.float64)
    aggw_tk = np.zeros((T, K), np.float64)
    gains_tk = np.zeros((T, K), np.float32)
    rates_list = []
    times = np.zeros(T, np.float64)
    t_wall = 0.0
    for t in range(T):
        devs = schedule.rounds[t]
        rates, budgets, round_time = _round_physics(
            devs, schedule.powers[t], schedule.rates[t], t, gains, cell,
            uplink, dl_time,
        )
        k = len(devs)
        ksizes[t] = k
        dev_tk[t, :k] = devs
        budgets_tk[t, :k] = budgets
        aggw_tk[t, :k] = _agg_weights(sizes, devs)
        gains_tk[t, :k] = gains[t, list(devs)]
        rates_list.append(rates)
        t_wall += round_time
        times[t] = t_wall

    # the same per-round noise keys the per-round driver folds on the host
    # (zeros are never consumed outside the OTA uplink, but packing them
    # unconditionally keeps the plan shape uplink-independent)
    noise_keys = ota_lib.horizon_keys(cfg.seed, T)

    eval_idx = eval_sample_plan(
        len(dataset.y_test), cfg.eval_sample, T, cfg.seed
    )
    return _HorizonPlan(params, payload, schedule, dev_tk, ksizes,
                        budgets_tk, aggw_tk, gains_tk, noise_keys,
                        rates_list, times, eval_idx)


def _horizon_statics(
    cfg: FLConfig, payload: int, eval_full: bool, cell, uplink,
) -> dict:
    """The static kwargs of the fl_engine horizon programs, from the config,
    and the one operand they share: the payload's frozen base (None for a
    payload that trains all its parameters; ``fl_engine.bound``), built on
    the device the first time a process asks for it.

    The OTA statics are pinned to zeros outside the OTA uplink so a
    noma/tdma run never retraces when ota_noise/ota_threshold configs vary.
    """
    ota = uplink == "ota"
    model = get_fl_model(cfg.model)
    return dict(
        lr=float(cfg.learning_rate), epochs=int(cfg.local_epochs),
        payload=int(payload), compress=cfg.compression == "adaptive",
        paper_exact=bool(cfg.paper_exact_range),
        use_pallas=bool(cfg.use_pallas), eval_full=bool(eval_full),
        model=model, topk=float(cfg.topk),
        ota=ota,
        ota_noise=float(cfg.ota_noise) if ota else 0.0,
        ota_threshold=float(cfg.ota_threshold) if ota else 0.0,
        pmax=float(cell.max_power_w) if ota else 0.0,
        base=frozen_base(model),
    )


def _build_banks(dataset, shards, cfg: FLConfig):
    """The device-resident client and test banks a scanned horizon
    program reads: host padding plus the upload, anew every call."""
    with spans.span("fl.bank"):
        bank = ClientBank.build(
            dataset.x_train, dataset.y_train, shards, cfg.batch_size
        )
        return bank, EvalBank.build(dataset.x_test, dataset.y_test)


def _eval_mask(num_rounds: int, eval_every: int) -> np.ndarray:
    """(T,) bool: which rounds evaluate — same cadence rule as the host
    loop, final round always included."""
    return np.array(
        [t % eval_every == 0 or t == num_rounds - 1
         for t in range(num_rounds)]
    )


def _stack_plans(plans, bank, num_rounds):
    """Stack per-instance plans along a leading axis for vmap/shard_map.

    Returns ``(params_s, dev, bud, agg, gains, keys, eidx, eval_full, nb)``
    where ``nb`` is the sweep-wide max scheduled batch count (one static
    shape for every instance — the padding batches contribute exactly-zero
    gradients).
    """
    # stack on the host: jnp.stack compiles one concatenate program per
    # leaf shape AND per sweep width, so the XLA program count would vary
    # with the number of instances (the compile-count sanitizer tests pin
    # it constant); np.stack + device_put is a pure transfer
    params_s = jax.tree_util.tree_map(
        lambda *ls: jnp.asarray(np.stack([np.asarray(l) for l in ls])),
        *[p.params0 for p in plans]
    )
    dev = np.stack([p.dev_tk for p in plans])
    bud = np.stack([p.budgets_tk for p in plans])
    agg = np.stack([p.aggw_tk for p in plans])
    gains = np.stack([p.gains_tk for p in plans])
    keys = np.stack([p.noise_keys for p in plans])
    eval_full = plans[0].eval_idx is None
    if eval_full:
        # dummy single-row plan: the traced gather needs a concrete shape
        # even though eval_full short-circuits it out of the program
        eidx = np.zeros((len(plans), num_rounds, 1), np.int32)
    else:
        eidx = np.stack([p.eval_idx for p in plans])
    nb = max(
        max(bank.n_batches_for(g) for g in p.schedule.rounds) for p in plans
    )
    return params_s, dev, bud, agg, gains, keys, eidx, eval_full, nb


def _assemble_horizon_result(
    plan: _HorizonPlan, cfg: FLConfig, uplink, eval_mask, bits_tk, accs_t,
    final_params, progress=None, kept_tk=None,
) -> FLResult:
    """Per-round ``RoundLog`` list from the scan outputs + the host plan.

    Slices each round's (K,) scan row down to its true group size, rebuilds
    the compression ratios with the same helper the per-round engines call
    (honest sparse on-air ratios from ``kept_tk`` when the top-k stage is
    on), and forward-fills skipped-eval rounds' accuracy — the same logging
    contract :func:`run_federated_learning` produces, entry for entry.
    """
    logs = []
    acc_prev = None
    for t in range(cfg.num_rounds):
        k = int(plan.ksizes[t])
        bits_r = np.asarray(bits_tk[t, :k])
        if k == 0:
            ratios = np.zeros(0)
        elif cfg.compression == "adaptive" and cfg.topk < 1.0:
            ratios = compression.sparse_compression_ratio(
                plan.payload, np.asarray(kept_tk[t, :k]), bits_r,
                plan.payload // 32,
            )
        elif cfg.compression == "adaptive":
            ratios = np.asarray(
                qlib.compression_ratio(
                    plan.payload, np.asarray(plan.budgets_tk[t, :k], np.float64)
                ),
                np.float64,
            )
        else:
            ratios = np.ones(k)
        acc = float(accs_t[t]) if eval_mask[t] else acc_prev
        acc_prev = acc
        log = RoundLog(
            t, tuple(plan.schedule.rounds[t]), np.asarray(plan.rates[t]),
            bits_r, ratios, acc, float(plan.times[t]),
        )
        logs.append(log)
        if progress:
            progress(log)
    scheme = f"{uplink}/{cfg.scheduler}/{cfg.power_mode}/{cfg.compression}"
    return FLResult(logs, final_params, scheme)


@_spanned("fl.horizon")
def run_horizon_scanned(
    dataset,
    shards: list,
    cell: chan.CellConfig,
    cfg: FLConfig,
    *,
    uplink: Optional[str] = None,
    schedule: Optional[scheduling.Schedule] = None,
    eval_every: int = 1,
    progress: Optional[Callable[[RoundLog], None]] = None,
) -> FLResult:
    """One whole horizon as ONE device program.

    The tentpole driver behind ``cfg.horizon = "scan"``.  For precomputed
    schedules all host work (schedule, rates, budgets, weights, timing)
    happens up front in :func:`_horizon_setup`; training + quantization +
    aggregation + eval for all T rounds then run as a single ``lax.scan``
    dispatch (:func:`fl_engine.run_horizon`).  Online policies with the
    traced protocol route to :func:`_run_horizon_online` instead, which
    folds selection / power allocation / budget math into the same scan
    (one host sync per horizon).  Same logs as the per-round driver —
    identical schedules/bits/rates/times, f32-tolerance accuracies — which
    ``tests/test_fl_scan.py`` pins across the uplink x compression x
    policy grid (tests/test_ota.py adds the OTA row, where even the
    accuracies are bit-identical: both drivers feed the same noise keys;
    tests/test_policy_scan.py adds the online-policy grid).
    """
    uplink = cfg.uplink if uplink is None else uplink
    ota_lib.check_uplink(
        uplink, compression=cfg.compression, topk=cfg.topk,
        power_mode=cfg.power_mode,
    )
    if (
        schedule is None
        and scheduling.policy_is_online(cfg.scheduler)
        and scheduling.policy_is_traced(cfg.scheduler)
    ):
        if cfg.power_mode == "mapel":
            # mirror the FLConfig gate for direct calls: the polyblock
            # search is host-iterative and cannot run inside the scan
            raise ValueError(
                errors.ERR_SCAN_ONLINE_MAPEL.format(scheduler=cfg.scheduler)
            )
        return _run_horizon_online(
            dataset, shards, cell, cfg, uplink=uplink,
            eval_every=eval_every, progress=progress,
        )
    plan = _horizon_setup(dataset, shards, cell, cfg, uplink, schedule)
    bank, ebank = _build_banks(dataset, shards, cfg)

    T = cfg.num_rounds
    eval_mask = _eval_mask(T, eval_every)
    eval_full = plan.eval_idx is None
    eidx = (np.zeros((T, 1), np.int32) if eval_full else plan.eval_idx)
    nb = max(bank.n_batches_for(g) for g in plan.schedule.rounds)

    with spans.span("fl.dispatch"):
        final, bits_tk, kept_tk, accs_t = fl_engine.run_horizon(
            plan.params0,
            jnp.asarray(plan.dev_tk),
            jnp.asarray(plan.budgets_tk),
            jnp.asarray(plan.aggw_tk, jnp.float32),
            jnp.asarray(plan.gains_tk),
            jnp.asarray(plan.noise_keys),
            jnp.asarray(eval_mask),
            jnp.asarray(eidx),
            bank.xb, bank.yb, ebank.xe, ebank.ye,
            nb=int(nb),
            **_horizon_statics(cfg, plan.payload, eval_full, cell, uplink),
        )
    with spans.span("fl.sync"):
        bits_tk, kept_tk, accs_t = jax.device_get((bits_tk, kept_tk, accs_t))
    with spans.span("fl.replay"):
        return _assemble_horizon_result(
            plan, cfg, uplink, eval_mask, bits_tk, accs_t, final, progress,
            kept_tk=kept_tk,
        )


# --------------------------------------------------------------------------
# Online-policy scanned horizons (the traced protocol's host driver)
# --------------------------------------------------------------------------

@dataclasses.dataclass
class _OnlinePlan:
    """Host precompute for one *online-policy* scanned instance.

    Unlike :class:`_HorizonPlan` there is no schedule to pack — selection
    happens inside the device program — so the plan carries the raw
    physics the traced policy and the post-sync log reconstruction both
    consume: the full (T, M) channel table, the data weights/sizes, and
    the policy's host aux (the f32 solo-rate table from ``init_traced``).
    """

    params0: dict                # freshly initialized model
    payload: int                 # I: full-precision payload bits
    gains: np.ndarray            # (T, M) float64 channel amplitudes
    weights: np.ndarray          # (M,) float64 data weights
    sizes: np.ndarray            # (M,) float64 shard sizes
    solo: np.ndarray             # (T, M) float32 policy aux (init_traced)
    noise_keys: np.ndarray       # (T, 2) uint32 OTA receiver-noise keys
    dl_time: float               # downlink broadcast seconds per round
    eval_idx: "np.ndarray | None"  # (T, n) eval sample plan; None = full set


def _traced_policy_config(cell, cfg: FLConfig) -> scheduling.PolicyConfig:
    """The PolicyConfig passed as a *static* jit argument to the online
    horizon programs: the fields no traced policy reads (seed, host
    scheduler backend) are pinned so program identity depends only on the
    physics (K, power mode, pmax, noise power, ota_noise) — a seed sweep
    reuses one compiled program."""
    return dataclasses.replace(
        policy_config(cell, cfg), seed=0, backend="numpy"
    )


def _online_statics(cfg: FLConfig, cell, uplink, policy) -> dict:
    """The online-only static kwargs of fl_engine.run_horizon_online
    (merged with :func:`_horizon_statics` at the call sites)."""
    return dict(
        scheduler=cfg.scheduler,
        pcfg=_traced_policy_config(cell, cfg),
        uplink=uplink,
        budget_scale=float(cell.bandwidth_hz) * float(cell.slot_seconds),
        need_norms=bool(getattr(policy, "needs_norms", True)),
    )


@_spanned("fl.plan")
def _online_horizon_setup(dataset, shards, cell, cfg: FLConfig, uplink):
    """Host precompute for one online scanned instance.

    Mirrors :func:`run_federated_learning`'s setup exactly — same PRNG
    folds, same downlink model — and asks the policy's ``init_traced``
    for its host aux (the f64-computed, f32-cast solo-rate table), so the
    traced selection ranks the same numbers the per-round driver's
    ``select_round`` does.
    """
    key = jax.random.PRNGKey(cfg.seed)
    params = get_fl_model(cfg.model).init(key)
    payload = tree_count(params) * 32

    sizes = np.array([len(s) for s in shards], dtype=np.float64)
    weights = sizes / sizes.sum()

    dist = chan.sample_positions(jax.random.fold_in(key, 1), cell)
    gains = np.asarray(
        chan.sample_round_channels(jax.random.fold_in(key, 2), dist, cell,
                                   cfg.num_rounds)
    )

    with spans.span("fl.schedule"):
        policy = scheduling.get_policy(cfg.scheduler)
        aux = policy.init_traced(gains, weights, policy_config(cell, cfg))

    dl_gains = chan.large_scale_gain(dist, cell)
    dl_time = float(chan.downlink_time_seconds(payload, dl_gains, cell))
    noise_keys = ota_lib.horizon_keys(cfg.seed, cfg.num_rounds)
    eval_idx = eval_sample_plan(
        len(dataset.y_test), cfg.eval_sample, cfg.num_rounds, cfg.seed
    )
    return _OnlinePlan(params, payload, gains, weights, sizes, aux["solo"],
                       noise_keys, dl_time, eval_idx)


def _finalize_online_plan(
    plan: _OnlinePlan, cfg: FLConfig, cell, uplink, dev_tk, mask_tk,
) -> _HorizonPlan:
    """Rebuild the host-f64 log tensors from the traced schedule.

    After the horizon's single ``device_get``, the realized (T, K) device
    ids + validity masks replay through the exact host calls the per-round
    driver makes — ``scheduling.finalize_round`` for powers/rates,
    :func:`_round_physics` for budgets/times — so the logged f64 values
    are bit-identical to per-round's *by construction* (the in-program f32
    rates priced the budgets the bits were computed from; the logs never
    read those).
    """
    allocator = power_lib.make_power_allocator(
        cfg.power_mode, cell.max_power_w, cell.noise_power_w
    )
    T, K = dev_tk.shape
    rounds, powers_l, rates_raw = [], [], []
    total = 0.0
    for t in range(T):
        devs = tuple(int(d) for d in dev_tk[t][mask_tk[t]])
        p_k, r_k = scheduling.finalize_round(
            devs, t, plan.gains, plan.weights, allocator, cell.noise_power_w
        )
        rounds.append(devs)
        powers_l.append(p_k)
        rates_raw.append(r_k)
        if devs:
            total += float(
                np.sum(plan.weights[np.asarray(devs, np.intp)] * r_k)
            )
    schedule = scheduling.Schedule(
        rounds, powers_l, rates_raw, total, cfg.scheduler, True
    )

    dev_out = np.zeros((T, K), np.int32)
    ksizes = np.zeros(T, np.intp)
    budgets_tk = np.zeros((T, K), np.float64)
    aggw_tk = np.zeros((T, K), np.float64)
    gains_tk = np.zeros((T, K), np.float32)
    rates_list = []
    times = np.zeros(T, np.float64)
    t_wall = 0.0
    for t in range(T):
        devs = rounds[t]
        rates, budgets, round_time = _round_physics(
            devs, powers_l[t], rates_raw[t], t, plan.gains, cell, uplink,
            plan.dl_time,
        )
        k = len(devs)
        ksizes[t] = k
        dev_out[t, :k] = devs
        budgets_tk[t, :k] = budgets
        aggw_tk[t, :k] = _agg_weights(plan.sizes, devs)
        gains_tk[t, :k] = plan.gains[t, list(devs)]
        rates_list.append(rates)
        t_wall += round_time
        times[t] = t_wall
    return _HorizonPlan(plan.params0, plan.payload, schedule, dev_out,
                        ksizes, budgets_tk, aggw_tk, gains_tk,
                        plan.noise_keys, rates_list, times, plan.eval_idx)


def _run_horizon_online(
    dataset,
    shards: list,
    cell: chan.CellConfig,
    cfg: FLConfig,
    *,
    uplink,
    eval_every: int = 1,
    progress: Optional[Callable[[RoundLog], None]] = None,
) -> FLResult:
    """One online-policy horizon as ONE device program, ONE host sync.

    The scan body selects devices (traced policy), allocates powers,
    prices budgets, trains, quantizes, aggregates and evaluates; the
    single ``jax.device_get`` below is the horizon's only host round-trip,
    after which :func:`_finalize_online_plan` rebuilds the f64 logs.
    """
    plan = _online_horizon_setup(dataset, shards, cell, cfg, uplink)
    bank, ebank = _build_banks(dataset, shards, cfg)

    T = cfg.num_rounds
    eval_mask = _eval_mask(T, eval_every)
    eval_full = plan.eval_idx is None
    eidx = (np.zeros((T, 1), np.int32) if eval_full else plan.eval_idx)
    # the schedule is decided in-program: every device must fit the
    # gathered shape, so slice to the bank-wide max batch count (the
    # all-padding extra batches contribute exactly-zero gradients)
    nb = bank.n_batches_for(range(cell.num_devices))
    policy = scheduling.get_policy(cfg.scheduler)

    with spans.span("fl.dispatch"):
        out = fl_engine.run_horizon_online(
            plan.params0,
            jnp.asarray(plan.solo),
            jnp.asarray(plan.gains, jnp.float32),
            jnp.asarray(plan.weights, jnp.float32),
            jnp.asarray(plan.sizes, jnp.float32),
            jnp.asarray(plan.noise_keys),
            jnp.asarray(eval_mask), jnp.asarray(eidx),
            bank.xb, bank.yb, ebank.xe, ebank.ye,
            nb=int(nb),
            **_online_statics(cfg, cell, uplink, policy),
            **_horizon_statics(cfg, plan.payload, eval_full, cell, uplink),
        )
    # ONE host sync for the whole horizon: schedule, bits, accuracies and
    # the final model come back together
    with spans.span("fl.sync"):
        final, dev_tk, mask_tk, bits_tk, kept_tk, accs_t = jax.device_get(out)
    with spans.span("fl.replay"):
        hplan = _finalize_online_plan(plan, cfg, cell, uplink, dev_tk, mask_tk)
        return _assemble_horizon_result(
            hplan, cfg, uplink, eval_mask, bits_tk, accs_t, final, progress,
            kept_tk=kept_tk,
        )


def _stack_online_plans(plans):
    """Host-stack per-instance online plans (same np.stack-not-jnp.stack
    rationale as :func:`_stack_plans`): returns
    ``(params_s, solo, gains_f32, keys, eidx, eval_full)``."""
    params_s = jax.tree_util.tree_map(
        lambda *ls: jnp.asarray(np.stack([np.asarray(l) for l in ls])),
        *[p.params0 for p in plans]
    )
    solo = np.stack([p.solo for p in plans])
    gains = np.stack([p.gains for p in plans]).astype(np.float32)
    keys = np.stack([p.noise_keys for p in plans])
    eval_full = plans[0].eval_idx is None
    if eval_full:
        T = plans[0].solo.shape[0]
        eidx = np.zeros((len(plans), T, 1), np.int32)
    else:
        eidx = np.stack([p.eval_idx for p in plans])
    return params_s, solo, gains, keys, eidx, eval_full


def _run_horizon_vmapped_online(
    dataset, shards, cell, cfg: FLConfig, seeds, uplink, eval_every,
) -> list:
    """Online-policy seed sweep: S traced horizons, one dispatch, one sync."""
    plans = [
        _online_horizon_setup(
            dataset, shards, cell, dataclasses.replace(cfg, seed=s), uplink
        )
        for s in seeds
    ]
    bank, ebank = _build_banks(dataset, shards, cfg)

    T = cfg.num_rounds
    eval_mask = _eval_mask(T, eval_every)
    nb = bank.n_batches_for(range(cell.num_devices))
    policy = scheduling.get_policy(cfg.scheduler)

    with spans.span("fl.dispatch"):
        params_s, solo, gains, keys, eidx, eval_full = _stack_online_plans(
            plans
        )
        out = fl_engine.run_horizon_online_vmapped(
            params_s,
            jnp.asarray(solo), jnp.asarray(gains),
            jnp.asarray(plans[0].weights, jnp.float32),
            jnp.asarray(plans[0].sizes, jnp.float32),
            jnp.asarray(keys), jnp.asarray(eval_mask), jnp.asarray(eidx),
            bank.xb, bank.yb, ebank.xe, ebank.ye,
            nb=int(nb),
            **_online_statics(cfg, cell, uplink, policy),
            **_horizon_statics(cfg, plans[0].payload, eval_full, cell,
                               uplink),
        )
    with spans.span("fl.sync"):
        final_s, dev_s, mask_s, bits_s, kept_s, accs_s = jax.device_get(out)
    results = []
    with spans.span("fl.replay"):
        for s, plan in enumerate(plans):
            scfg = dataclasses.replace(cfg, seed=int(seeds[s]))
            hplan = _finalize_online_plan(
                plan, scfg, cell, uplink, dev_s[s], mask_s[s]
            )
            fp = jax.tree_util.tree_map(
                lambda l, s=s: jnp.asarray(l[s]), final_s
            )
            results.append(_assemble_horizon_result(
                hplan, scfg, uplink, eval_mask, bits_s[s], accs_s[s], fp,
                kept_tk=kept_s[s],
            ))
    return results


def _run_cell_sweep_online(
    dataset, shards, cell, cfg: FLConfig, C, S, uplink, eval_every,
    shards_n, inst_seeds,
) -> list:
    """Online-policy (cells x seeds) grid — traced horizons end to end.

    Same two execution strategies as :func:`run_cell_sweep`: a 1-shard
    mesh dispatches one :func:`fl_engine.run_horizon_online` program per
    instance (shared statics -> one compiled scan for the whole grid);
    multi-shard runs the stacked (C, S) program under ``shard_map``.
    """
    flat = [
        _online_horizon_setup(
            dataset, shards, cell,
            dataclasses.replace(cfg, seed=inst_seeds[c][s]), uplink,
        )
        for c in range(C)
        for s in range(S)
    ]
    bank, ebank = _build_banks(dataset, shards, cfg)

    T = cfg.num_rounds
    eval_mask = _eval_mask(T, eval_every)
    with spans.span("fl.dispatch"):
        params_f, solo, gains, keys, eidx, eval_full = _stack_online_plans(
            flat
        )
    nb = bank.n_batches_for(range(cell.num_devices))
    policy = scheduling.get_policy(cfg.scheduler)
    weights_j = jnp.asarray(flat[0].weights, jnp.float32)
    sizes_j = jnp.asarray(flat[0].sizes, jnp.float32)
    statics = dict(
        **_online_statics(cfg, cell, uplink, policy),
        **_horizon_statics(cfg, flat[0].payload, eval_full, cell, uplink),
    )

    def finish(i, c, s, final_np, dev_i, mask_i, bits_i, kept_i, accs_i):
        scfg = dataclasses.replace(cfg, seed=inst_seeds[c][s])
        with spans.span("fl.replay"):
            hplan = _finalize_online_plan(
                flat[i], scfg, cell, uplink, dev_i, mask_i
            )
            fp = jax.tree_util.tree_map(jnp.asarray, final_np)
            return _assemble_horizon_result(
                hplan, scfg, uplink, eval_mask, bits_i, accs_i, fp,
                kept_tk=kept_i,
            )

    if shards_n == 1:
        emask_j = jnp.asarray(eval_mask)
        results = []
        for c in range(C):
            row = []
            for s in range(S):
                i = c * S + s
                with spans.span("fl.dispatch"):
                    out = fl_engine.run_horizon_online(
                        flat[i].params0,
                        jnp.asarray(solo[i]), jnp.asarray(gains[i]),
                        weights_j, sizes_j,
                        jnp.asarray(keys[i]), emask_j, jnp.asarray(eidx[i]),
                        bank.xb, bank.yb, ebank.xe, ebank.ye,
                        nb=int(nb), **statics,
                    )
                with spans.span("fl.sync"):
                    final, dev_i, mask_i, bits_i, kept_i, accs_i = (
                        jax.device_get(out)
                    )
                row.append(finish(
                    i, c, s, final, dev_i, mask_i, bits_i, kept_i, accs_i
                ))
            results.append(row)
        return results

    def cs(a):
        return a.reshape(C, S, *a.shape[1:])

    solo_cs, gains_cs = cs(solo), cs(gains)
    keys_cs, eidx_cs = cs(keys), cs(eidx)
    params_cs = jax.tree_util.tree_map(
        lambda l: l.reshape(C, S, *l.shape[1:]), params_f
    )
    pad = (-C) % shards_n
    if pad:
        solo_cs = np.concatenate([solo_cs, solo_cs[:pad]])
        gains_cs = np.concatenate([gains_cs, gains_cs[:pad]])
        keys_cs = np.concatenate([keys_cs, keys_cs[:pad]])
        eidx_cs = np.concatenate([eidx_cs, eidx_cs[:pad]])
        params_cs = jax.tree_util.tree_map(
            lambda l: jnp.concatenate([l, l[:pad]]), params_cs
        )

    with spans.span("fl.dispatch"):
        out = fl_engine.run_horizon_online_sharded(
            params_cs,
            jnp.asarray(solo_cs), jnp.asarray(gains_cs),
            jnp.asarray(keys_cs), jnp.asarray(eval_mask),
            jnp.asarray(eidx_cs), weights_j, sizes_j,
            bank.xb, bank.yb, ebank.xe, ebank.ye,
            shards=shards_n, nb=int(nb), **statics,
        )
    with spans.span("fl.sync"):
        final_cs, dev_cs, mask_cs, bits_cs, kept_cs, accs_cs = (
            jax.device_get(out)
        )
    results = []
    for c in range(C):
        row = []
        for s in range(S):
            fp = jax.tree_util.tree_map(
                lambda l, c=c, s=s: l[c, s], final_cs
            )
            row.append(finish(
                c * S + s, c, s, fp, dev_cs[c, s], mask_cs[c, s],
                bits_cs[c, s], kept_cs[c, s], accs_cs[c, s],
            ))
        results.append(row)
    return results


@_spanned("fl.horizon")
def run_horizon_vmapped(
    dataset,
    shards: list,
    cell: chan.CellConfig,
    cfg: FLConfig,
    *,
    seeds,
    uplink: Optional[str] = None,
    eval_every: int = 1,
) -> list:
    """A whole seed sweep — S independent scanned horizons, one dispatch.

    Each seed gets its own model init, channel draws, schedule and eval
    plan (``dataclasses.replace(cfg, seed=s)``); the client bank and test
    set are shared.  Returns one :class:`FLResult` per seed, in order —
    row s is the same program :func:`run_horizon_scanned` runs for that
    seed alone (the row-0 identity test pins this).
    """
    uplink = cfg.uplink if uplink is None else uplink
    ota_lib.check_uplink(
        uplink, compression=cfg.compression, topk=cfg.topk,
        power_mode=cfg.power_mode,
    )
    seeds = [int(s) for s in seeds]
    if not seeds:
        raise ValueError("seeds must be a non-empty sequence")
    if (scheduling.policy_is_online(cfg.scheduler)
            and scheduling.policy_is_traced(cfg.scheduler)):
        if cfg.power_mode == "mapel":
            raise ValueError(
                errors.ERR_SCAN_ONLINE_MAPEL.format(scheduler=cfg.scheduler)
            )
        return _run_horizon_vmapped_online(
            dataset, shards, cell, cfg, seeds, uplink, eval_every
        )
    plans = [
        _horizon_setup(
            dataset, shards, cell, dataclasses.replace(cfg, seed=s), uplink,
            None,
        )
        for s in seeds
    ]
    bank, ebank = _build_banks(dataset, shards, cfg)

    T = cfg.num_rounds
    eval_mask = _eval_mask(T, eval_every)
    with spans.span("fl.dispatch"):
        params_s, dev, bud, agg, gains, keys, eidx, eval_full, nb = (
            _stack_plans(plans, bank, T)
        )
        final_s, bits_stk, kept_stk, accs_st = fl_engine.run_horizon_vmapped(
            params_s,
            jnp.asarray(dev), jnp.asarray(bud), jnp.asarray(agg, jnp.float32),
            jnp.asarray(gains), jnp.asarray(keys),
            jnp.asarray(eval_mask), jnp.asarray(eidx),
            bank.xb, bank.yb, ebank.xe, ebank.ye,
            nb=int(nb),
            **_horizon_statics(cfg, plans[0].payload, eval_full, cell,
                               uplink),
        )
    # unstack on the host for the same reason _stack_plans stacks there:
    # a traced l[s] compiles one dynamic_slice program per leaf shape per
    # sweep width, making the program count depend on the seed count
    with spans.span("fl.sync"):
        bits_np, kept_np, accs_np, final_np = jax.device_get(
            (bits_stk, kept_stk, accs_st, final_s)
        )
    results = []
    with spans.span("fl.replay"):
        for s, plan in enumerate(plans):
            fp = jax.tree_util.tree_map(
                lambda l, s=s: jnp.asarray(l[s]), final_np
            )
            results.append(_assemble_horizon_result(
                plan, dataclasses.replace(cfg, seed=seeds[s]), uplink,
                eval_mask, bits_np[s], accs_np[s], fp, kept_tk=kept_np[s],
            ))
    return results


@_spanned("fl.horizon")
def run_cell_sweep(
    dataset,
    shards: list,
    cell: chan.CellConfig,
    cfg: FLConfig,
    *,
    num_cells: int,
    seeds_per_cell: int = 1,
    uplink: Optional[str] = None,
    eval_every: int = 1,
    cell_shards: Optional[int] = None,
) -> list:
    """A (cells x seeds) grid of independent simulations, cell axis sharded.

    Each of the C * S instances is one scanned horizon with its own seed
    (``cfg.seed + c * seeds_per_cell + s`` — cells are just disjoint seed
    blocks of the same cell geometry; the draws differ, the physics config
    doesn't).

    With ``cell_shards > 1`` the stacked (C, S, ...) program runs under
    ``shard_map`` over :func:`repro.launch.mesh.cell_mesh` (more shards
    than local devices raise ``errors.ERR_SHARDS_EXCEED_DEVICES``; nothing
    is clamped in silence), C padded up to a multiple of the mesh
    (repeating leading cells, unpadded on return) — each mesh device runs
    its own block of vmapped horizons in parallel.  On a trivial 1-device
    mesh (the default) the sweep instead dispatches one
    :func:`fl_engine.run_horizon` program per instance: all instances
    share the bank, the test set and ONE compiled scan (sweep-wide static
    shapes), and on a single core the sequential dispatches beat the
    double-vmapped program, whose instance-batched per-round gathers blow
    the cache with no parallelism in return (BENCH_cells.json).  Both
    paths produce identical results (pinned by tests/test_fl_scan.py).

    Returns ``results[c][s]`` :class:`FLResult` grids.
    """
    uplink = cfg.uplink if uplink is None else uplink
    ota_lib.check_uplink(
        uplink, compression=cfg.compression, topk=cfg.topk,
        power_mode=cfg.power_mode,
    )
    C, S = int(num_cells), int(seeds_per_cell)
    if C < 1 or S < 1:
        raise ValueError(f"need num_cells >= 1 and seeds_per_cell >= 1, "
                         f"got ({num_cells}, {seeds_per_cell})")
    shards_n = 1 if cell_shards is None else max(1, int(cell_shards))
    if shards_n > jax.local_device_count():
        raise ValueError(errors.ERR_SHARDS_EXCEED_DEVICES.format(
            option="cell_shards", requested=shards_n,
            available=jax.local_device_count(),
        ))

    inst_seeds = [[cfg.seed + c * S + s for s in range(S)] for c in range(C)]
    if (scheduling.policy_is_online(cfg.scheduler)
            and scheduling.policy_is_traced(cfg.scheduler)):
        if cfg.power_mode == "mapel":
            raise ValueError(
                errors.ERR_SCAN_ONLINE_MAPEL.format(scheduler=cfg.scheduler)
            )
        return _run_cell_sweep_online(
            dataset, shards, cell, cfg, C, S, uplink, eval_every, shards_n,
            inst_seeds,
        )
    plans = [
        [
            _horizon_setup(
                dataset, shards, cell,
                dataclasses.replace(cfg, seed=inst_seeds[c][s]), uplink, None,
            )
            for s in range(S)
        ]
        for c in range(C)
    ]
    bank, ebank = _build_banks(dataset, shards, cfg)

    T = cfg.num_rounds
    eval_mask = _eval_mask(T, eval_every)
    flat = [p for row in plans for p in row]
    with spans.span("fl.dispatch"):
        params_f, dev, bud, agg, gains, keys, eidx, eval_full, nb = (
            _stack_plans(flat, bank, T)
        )
    statics = _horizon_statics(cfg, flat[0].payload, eval_full, cell, uplink)

    if shards_n == 1:
        # Single-device fast path: one run_horizon dispatch per instance.
        # Sweep-wide nb keeps the shapes static, so every instance reuses
        # the first one's compiled program.
        emask_j = jnp.asarray(eval_mask)
        results = []
        for c in range(C):
            row = []
            for s in range(S):
                i = c * S + s
                with spans.span("fl.dispatch"):
                    final, bits_tk, kept_tk, accs_t = fl_engine.run_horizon(
                        flat[i].params0,
                        jnp.asarray(dev[i]), jnp.asarray(bud[i]),
                        jnp.asarray(agg[i], jnp.float32),
                        jnp.asarray(gains[i]), jnp.asarray(keys[i]),
                        emask_j, jnp.asarray(eidx[i]),
                        bank.xb, bank.yb, ebank.xe, ebank.ye,
                        nb=int(nb), **statics,
                    )
                with spans.span("fl.sync"):
                    bits_tk, kept_tk, accs_t = jax.device_get(
                        (bits_tk, kept_tk, accs_t)
                    )
                with spans.span("fl.replay"):
                    row.append(_assemble_horizon_result(
                        flat[i],
                        dataclasses.replace(cfg, seed=inst_seeds[c][s]),
                        uplink, eval_mask, bits_tk, accs_t, final,
                        kept_tk=kept_tk,
                    ))
            results.append(row)
        return results

    def cs(a):
        return a.reshape(C, S, *a.shape[1:])

    dev, bud, agg = cs(dev), cs(bud), cs(agg)
    gains, keys, eidx = cs(gains), cs(keys), cs(eidx)
    params_cs = jax.tree_util.tree_map(
        lambda l: l.reshape(C, S, *l.shape[1:]), params_f
    )
    pad = (-C) % shards_n
    if pad:
        # shard_map needs C divisible by the mesh: repeat leading cells
        # (their results are sliced off below, so the waste is bounded by
        # shards - 1 duplicate cell programs)
        dev = np.concatenate([dev, dev[:pad]])
        bud = np.concatenate([bud, bud[:pad]])
        agg = np.concatenate([agg, agg[:pad]])
        gains = np.concatenate([gains, gains[:pad]])
        keys = np.concatenate([keys, keys[:pad]])
        eidx = np.concatenate([eidx, eidx[:pad]])
        params_cs = jax.tree_util.tree_map(
            lambda l: jnp.concatenate([l, l[:pad]]), params_cs
        )

    with spans.span("fl.dispatch"):
        final_cs, bits_cstk, kept_cstk, accs_cst = (
            fl_engine.run_horizon_sharded(
                params_cs,
                jnp.asarray(dev), jnp.asarray(bud),
                jnp.asarray(agg, jnp.float32),
                jnp.asarray(gains), jnp.asarray(keys),
                jnp.asarray(eval_mask), jnp.asarray(eidx),
                bank.xb, bank.yb, ebank.xe, ebank.ye,
                shards=shards_n, nb=int(nb), **statics,
            )
        )
    with spans.span("fl.sync"):
        bits_np, kept_np, accs_np = jax.device_get(
            (bits_cstk, kept_cstk, accs_cst)
        )
    results = []
    with spans.span("fl.replay"):
        for c in range(C):
            row = []
            for s in range(S):
                fp = jax.tree_util.tree_map(
                    lambda l, c=c, s=s: l[c, s], final_cs
                )
                row.append(_assemble_horizon_result(
                    plans[c][s],
                    dataclasses.replace(cfg, seed=inst_seeds[c][s]), uplink,
                    eval_mask, bits_np[c, s], accs_np[c, s], fp,
                    kept_tk=kept_np[c, s],
                ))
            results.append(row)
    return results
