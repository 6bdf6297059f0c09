"""Bring-up smoke run of the FL horizon on a TPU.

    python chip_smoke.py             # one chip: the paper horizon and its checks
    python chip_smoke.py --chips 4   # four chips: the sharded cell sweep only

Drives the paper's full configuration (M = 300 devices, K = 3 per round,
T = 35 rounds, LeNet-300-100 on the 12,000-sample MNIST-like set with
Dirichlet shards, the setting of ``examples/fl_noma_mnist.py``) through the
normal entry points of ``repro.core.fl`` and checks every result against the
repository's own references:

  paper-horizon  the scanned horizon (GWMIN on the device, MAPEL power,
                 adaptive DoReFa, NOMA) against the per-round driver with the
                 numpy scheduler: equal schedules, bits, rates and times,
                 accuracies within the engines' f32 tolerance, finite and
                 rising.
  online-scan    the update-aware policy selected inside the scan against the
                 per-round driver: equal devices and bits.
  pallas         the aggregate and OTA superpose kernels against the einsum
                 on identical inputs at LeNet width; then whole horizons
                 with the kernels against the einsum (adaptive DoReFa,
                 uncompressed, OTA): equal plans, accuracies within
                 tolerance, and for the uncompressed horizon final
                 parameters within the CPU test's limits.
  cell-sweep     (``--chips 4`` only) ``fl.run_cell_sweep`` sharded over four
                 chips against the same sweep on one: identical plans,
                 accuracies within tolerance, the sharded run repeatable.

Data come from ``make_mnist_like`` and weights from the model's own init, both
from ``--seed``.  Everything runs in this one process.  The script exits
non-zero, and prints no result, when JAX finds no TPU or any check fails.
The last line of its output is one JSON object naming the device.  The
timings it prints are smoke timings of single runs, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import numpy as np  # noqa: E402

PAPER_DEVICES = 300     # M
PAPER_ROUNDS = 35       # T
PAPER_SAMPLES = 12_000  # MNIST-like set, examples/fl_noma_mnist.py
GROUP_SIZE = 3          # K
LENET_PARAMS = 266_610  # LeNet-300-100 payload per client

# Tolerances of the CPU tests that pin the same comparisons.
ACC_ATOL = 0.02             # tests/test_fl_engine.py: two programs, f32
PARAM_MEAN_ATOL = 1e-6      # tests/test_fl_engine.py
PALLAS_PARAM_MAX_ATOL = 1e-4  # tests/test_fl_engine.py, Pallas vs einsum


class CheckFailed(RuntimeError):
    pass


def check(ok, message):
    if not ok:
        raise CheckFailed(message)


@dataclasses.dataclass
class World:
    dataset: object
    cell: object
    shards: list


def paper_world(num_devices, num_samples, seed):
    from repro.core import channel
    from repro.data import dirichlet_partition, make_mnist_like

    ds = make_mnist_like(num_samples=num_samples, seed=seed)
    shards = dirichlet_partition(ds.y_train, num_devices, seed=seed)
    return World(ds, channel.CellConfig(num_devices=num_devices), shards)


def paper_config(num_devices, num_rounds, seed):
    from repro.config import FLConfig

    return FLConfig(
        num_devices=num_devices, group_size=GROUP_SIZE, num_rounds=num_rounds,
        learning_rate=0.01, batch_size=10,
        scheduler="lazy-gwmin", scheduler_backend="jax", power_mode="mapel",
        compression="adaptive", uplink="noma", fl_engine="batched",
        horizon="scan", seed=seed,
    )


def run(world, cfg):
    from repro.core import fl

    return fl.run_federated_learning(
        world.dataset, world.shards, world.cell, cfg
    )


def timed(label, fn, *, repeat):
    """Call ``fn`` ``repeat`` times; print the first (compile included) and
    the last call's wall time.  Returns the last result."""
    seconds = []
    for _ in range(repeat):
        t0 = time.perf_counter()
        out = fn()
        seconds.append(time.perf_counter() - t0)
    if repeat > 1:
        print(f"[{label}] smoke timing: first call {seconds[0]:.3f} s "
              f"(compile included), second call {seconds[-1]:.3f} s "
              f"(steady), compile ~{seconds[0] - seconds[-1]:.3f} s")
    else:
        print(f"[{label}] smoke timing: one call {seconds[0]:.3f} s "
              f"(compile included)")
    return out


def _first_diff(xs, ys):
    for i, (x, y) in enumerate(zip(xs, ys)):
        if not np.array_equal(np.asarray(x), np.asarray(y)):
            return i
    return None


def check_same_plan(a, b, label, *, rates=True):
    """Schedules, bits (and rates and times) equal, round for round."""
    t = _first_diff([l.devices for l in a.logs], [l.devices for l in b.logs])
    check(t is None, f"{label}: schedules differ from round {t}: "
          f"{a.logs[t].devices if t is not None else ''} vs "
          f"{b.logs[t].devices if t is not None else ''}")
    t = _first_diff([l.bits for l in a.logs], [l.bits for l in b.logs])
    check(t is None, f"{label}: bits differ from round {t}")
    if rates:
        t = _first_diff([l.rates for l in a.logs], [l.rates for l in b.logs])
        check(t is None, f"{label}: rates differ from round {t}")
        check(np.array_equal(a.times(), b.times()), f"{label}: times differ")


def check_accuracies(a, b, label):
    gap = float(np.max(np.abs(a.accuracies() - b.accuracies())))
    print(f"[{label}] max |accuracy difference| {gap!r} (limit {ACC_ATOL})")
    check(gap <= ACC_ATOL, f"{label}: accuracies differ by {gap}")


def param_drift(a, b, label):
    """Mean and max |difference| of two runs' final parameters, printed."""
    import jax

    worst_mean = worst_max = 0.0
    for x, y in zip(jax.tree_util.tree_leaves(a.final_params),
                    jax.tree_util.tree_leaves(b.final_params)):
        d = np.abs(np.asarray(x, np.float64) - np.asarray(y, np.float64))
        worst_mean = max(worst_mean, float(d.mean()))
        worst_max = max(worst_max, float(d.max()))
    print(f"[{label}] final-parameter drift: mean {worst_mean!r}, "
          f"max {worst_max!r}")
    return worst_mean, worst_max


def check_params(a, b, label, *, max_atol):
    mean, worst = param_drift(a, b, label)
    check(mean < PARAM_MEAN_ATOL and worst < max_atol,
          f"{label}: final parameters drift (mean {mean} >= "
          f"{PARAM_MEAN_ATOL} or max {worst} >= {max_atol})")


def check_learning(res, label):
    accs = res.accuracies()
    check(np.all(np.isfinite(accs)), f"{label}: non-finite accuracies")
    check(accs[-1] > accs[0], f"{label}: accuracy did not rise "
          f"({accs[0]} -> {accs[-1]})")
    print(f"[{label}] accuracy round 0 {float(accs[0])!r}, "
          f"final {float(accs[-1])!r}")


def phase_paper_horizon(world, cfg):
    """The scanned paper horizon against the per-round numpy-scheduled run."""
    scan = timed("paper-horizon scan", lambda: run(world, cfg), repeat=2)
    ref = timed(
        "paper-horizon per-round (numpy scheduler)",
        lambda: run(world, dataclasses.replace(
            cfg, scheduler_backend="numpy", horizon="per-round")),
        repeat=1,
    )
    check_same_plan(scan, ref, "paper-horizon")
    check_accuracies(scan, ref, "paper-horizon")
    param_drift(scan, ref, "paper-horizon")
    check_learning(scan, "paper-horizon")
    return scan


def phase_online_scan(world, cfg):
    """update-aware selection inside the scan against the per-round driver."""
    online = dataclasses.replace(
        cfg, scheduler="update-aware", power_mode="max",
        scheduler_backend="numpy",
    )
    per_round = dataclasses.replace(online, horizon="per-round")
    scan = timed("online-scan scan", lambda: run(world, online), repeat=2)
    ref = timed("online-scan per-round", lambda: run(world, per_round),
                repeat=1)
    # The per-round driver ranks f64 scores on the host, the scan ranks f32
    # scores on the device, from update norms two programs computed: a
    # near-tie can rank differently.  Each such round is printed with the
    # per-round driver's scores; the check is on what each round chose.
    differ = [t for t, (a, b) in enumerate(zip(scan.logs, ref.logs))
              if a.devices != b.devices]
    if differ:
        scores = per_round_scores(world, per_round, set(differ))
        for t in differ:
            report_rank_flip(t, scan.logs[t].devices, ref.logs[t].devices,
                             scores[t])
    for t, (a, b) in enumerate(zip(scan.logs, ref.logs)):
        chosen_a = dict(zip(a.devices, np.asarray(a.bits).tolist()))
        chosen_b = dict(zip(b.devices, np.asarray(b.bits).tolist()))
        check(chosen_a == chosen_b,
              f"online-scan: round {t} chose devices:bits {chosen_a} in the "
              f"scan but {chosen_b} per round")
    print(f"[online-scan] {len(differ)} of {len(scan.logs)} rounds list the "
          f"same devices in another order; every round chose the same "
          f"devices with the same bits")
    check_learning(scan, "online-scan")


def per_round_scores(world, cfg, rounds):
    """The update-aware scores the per-round driver ranked at ``rounds``:
    a diagnostic rerun that records ``UpdateAwarePolicy._score``."""
    from repro.core import scheduling

    policy_cls = scheduling.UpdateAwarePolicy
    score = policy_cls._score
    seen = {}

    def recording(self, t, solo, obs):
        out = score(self, t, solo, obs)
        if t in rounds:
            seen[t] = np.asarray(out, np.float64)
        return out

    policy_cls._score = recording
    try:
        run(world, cfg)
    finally:
        policy_cls._score = score
    return seen


def report_rank_flip(t, scan_devices, ref_devices, score):
    devs = sorted(set(scan_devices) | set(ref_devices))
    vals = {d: float(score[d]) for d in devs}
    top = sorted(vals.values(), reverse=True)
    gap = min(abs(x - y) for x, y in zip(top, top[1:])) if len(top) > 1 else 0
    print(f"[online-scan] round {t}: scan {tuple(scan_devices)} vs per-round "
          f"{tuple(ref_devices)}; per-round f64 scores {vals}; smallest gap "
          f"{gap!r} ({gap / max(top):.3e} relative)")


def check_kernel_operators(seed, *, rtol=1e-5):
    """Both aggregation kernels against their einsum on identical inputs at
    the LeNet-300-100 width (K = 3 clients x 266,610 params).

    The bound is the kernels' CPU tests' rtol, taken relative to the
    largest |value|: at this width some sums cancel to near zero, where an
    elementwise relative bound would measure the cancellation, not the
    kernel's summation order."""
    import jax
    import jax.numpy as jnp

    from repro.core import ota as ota_lib
    from repro.core import quantization as qlib
    from repro.kernels.aggregate import weighted_aggregate_pallas

    rng = np.random.default_rng(seed)
    flat = jnp.asarray(
        rng.standard_normal((GROUP_SIZE, LENET_PARAMS)), jnp.float32
    )
    bits = jnp.asarray([4, 8, 16], jnp.int32)
    w = jnp.asarray([0.2, 0.3, 0.5], jnp.float32)

    @jax.jit
    def digital(flat):
        codes, scales, a = qlib.quantize_codes_batched(flat, bits)
        got = weighted_aggregate_pallas(codes, scales, w, levels=a)
        return got, jnp.einsum("k,kn->n", w / a * scales, codes)

    check_close(*digital(flat), "weighted aggregate kernel", rtol=rtol)

    # the OTA operator test's channel, power and noise (tests/test_ota.py)
    gains = jnp.asarray([1e-6, 2e-6, 5e-7], jnp.float32)
    key = jnp.asarray(ota_lib.horizon_keys(seed, 1)[0])
    kw = dict(pmax=0.01, noise_std=1e-8, threshold=0.0)
    got = ota_lib.superpose_tree({"p": flat}, gains, w, key, use_pallas=True,
                                 **kw)["p"]
    want = ota_lib.superpose_tree({"p": flat}, gains, w, key, **kw)["p"]
    check_close(got, want, "ota superpose kernel", rtol=rtol)


def check_close(got, want, label, *, rtol):
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    err = float(np.max(np.abs(got - want)))
    scale = float(np.max(np.abs(want)))
    print(f"[{label}] max |difference| {err!r} over {got.size} elements, "
          f"{err / scale!r} of the largest |value| (limit {rtol})")
    check(err <= rtol * scale,
          f"{label}: differs from the einsum by {err} > {rtol} x {scale}")


def phase_pallas(world, cfg, einsum_run):
    """Pallas kernels on the main path against the einsum runs."""
    check_kernel_operators(cfg.seed)
    # Whole horizons: kernel and einsum sum in another order, so the two
    # trajectories start ulps apart.  A delta that then lands on a DoReFa
    # round() boundary flips one quantization step, and the OTA uplink's
    # noisy updates part them too: over 35 rounds their final parameters
    # drift further than the kernels' own error, so those two drifts are
    # printed, not bounded.  The uncompressed digital horizon has neither
    # amplifier, and its drift is bounded by the CPU test's limits.
    pallas = timed(
        "pallas aggregate",
        lambda: run(world, dataclasses.replace(cfg, use_pallas=True)),
        repeat=2,
    )
    check_same_plan(pallas, einsum_run, "pallas aggregate")
    check_accuracies(pallas, einsum_run, "pallas aggregate")
    param_drift(pallas, einsum_run, "pallas aggregate")

    plain = dataclasses.replace(cfg, compression="none")
    plain_einsum = timed("uncompressed einsum", lambda: run(world, plain),
                         repeat=1)
    plain_pallas = timed(
        "uncompressed pallas",
        lambda: run(world, dataclasses.replace(plain, use_pallas=True)),
        repeat=2,
    )
    check_same_plan(plain_pallas, plain_einsum, "uncompressed pallas")
    check_accuracies(plain_pallas, plain_einsum, "uncompressed pallas")
    check_params(plain_pallas, plain_einsum, "uncompressed pallas",
                 max_atol=PALLAS_PARAM_MAX_ATOL)
    check_learning(plain_pallas, "uncompressed pallas")

    ota = dataclasses.replace(
        cfg, uplink="ota", ota_noise=1e-9, compression="none",
        power_mode="max",
    )
    ota_einsum = timed("ota einsum", lambda: run(world, ota), repeat=1)
    ota_pallas = timed(
        "ota pallas",
        lambda: run(world, dataclasses.replace(ota, use_pallas=True)),
        repeat=2,
    )
    check_same_plan(ota_pallas, ota_einsum, "ota pallas")
    check_accuracies(ota_pallas, ota_einsum, "ota pallas")
    param_drift(ota_pallas, ota_einsum, "ota pallas")
    check_learning(ota_pallas, "ota pallas")


def phase_cell_sweep(world, cfg, *, shards, num_cells=4, seeds_per_cell=2):
    """The cells x seeds sweep sharded over ``shards`` chips against the
    same sweep on one chip.

    Schedules, bits, rates and times are planned on the host and must be
    identical; the sharded program must repeat itself bit for bit.  With
    one shard the sweep dispatches each instance's horizon alone, while
    the sharded program vmaps two seeds per chip: on the TPU the two
    programs differ by ulps, which DoReFa rounding amplifies (as between
    any two programs, see ``phase_pallas``), so their accuracies are held
    to the engines' f32 tolerance.  The per-cell seed sweep on one chip
    (``fl.run_horizon_vmapped``, the sharded program's per-chip body) is
    printed beside them to show whether sharding itself moves anything.
    """
    from repro.core import fl

    def sweep(n):
        return fl.run_cell_sweep(
            world.dataset, world.shards, world.cell, cfg,
            num_cells=num_cells, seeds_per_cell=seeds_per_cell,
            cell_shards=n,
        )

    one = timed("cell-sweep 1 shard", lambda: sweep(1), repeat=1)
    first = timed(f"cell-sweep {shards} shards, first call",
                  lambda: sweep(shards), repeat=1)
    many = timed(f"cell-sweep {shards} shards, second call",
                 lambda: sweep(shards), repeat=1)
    vmapped = timed("per-cell seed sweeps on one chip", lambda: [
        fl.run_horizon_vmapped(
            world.dataset, world.shards, world.cell, cfg,
            seeds=[cfg.seed + c * seeds_per_cell + s
                   for s in range(seeds_per_cell)],
        )
        for c in range(num_cells)
    ], repeat=1)
    # every instance's comparison is printed before any check can stop
    # the run, so a failing four-chip call still reports all of them
    pairs = [(f"cell {c} seed {s}", many[c][s], one[c][s], first[c][s],
              vmapped[c][s])
             for c in range(num_cells) for s in range(seeds_per_cell)]
    for label, a, b, _, v in pairs:
        for name, ref in (("1 shard", b), ("per-cell vmapped", v)):
            gap = float(np.max(np.abs(a.accuracies() - ref.accuracies())))
            print(f"[cell-sweep {label} vs {name}] max |accuracy "
                  f"difference| {gap!r}")
            param_drift(a, ref, f"cell-sweep {label} vs {name}")
    for label, a, b, again, _ in pairs:
        label = f"cell-sweep {label}"
        check_same_plan(a, b, label)
        check_accuracies(a, b, label)
        check(np.array_equal(a.accuracies(), again.accuracies())
              and param_drift(a, again, f"{label} repeat") == (0.0, 0.0),
              f"{label}: the sharded sweep did not repeat itself")
        check_learning(a, label)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the single-chip phases; 4: only the cell sweep "
                         "sharded over four chips and its one-chip reference")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"no TPU found (JAX platform {platform!r}); not running on it",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} TPU chips; "
              f"JAX sees {len(devices)}", file=sys.stderr)
        return 1
    kind = devices[0].device_kind
    print(f"device: {platform} {kind!r} x{len(devices)}")

    from repro.utils.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}")

    world = paper_world(PAPER_DEVICES, PAPER_SAMPLES, args.seed)
    cfg = paper_config(PAPER_DEVICES, PAPER_ROUNDS, args.seed)
    if args.chips == 1:
        scan = phase_paper_horizon(world, cfg)
        phase_online_scan(world, cfg)
        phase_pallas(world, cfg, scan)
    else:
        phase_cell_sweep(world, cfg, shards=args.chips)

    stats = devices[0].memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
