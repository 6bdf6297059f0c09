"""Paper §IV experiment driver (the end-to-end example): M=300 devices,
K=3 per round, T=35 rounds, LeNet-300-100, non-iid data — reproducing the
Fig. 5 / Fig. 6 settings.

    PYTHONPATH=src python examples/fl_noma_mnist.py [--fast] \
        [--scheduler NAME] [--power mapel|max|ota-align] \
        [--uplink noma|tdma|ota] [--ota-noise STD] [--ota-threshold FRAC] \
        [--engine batched|legacy] [--pallas-agg] \
        [--horizon per-round|scan] [--seeds N] \
        [--model NAME] [--topk FRAC]

``--scheduler`` accepts any registered policy name (see
``repro.core.scheduling``): the paper's precomputed schedulers
(lazy-gwmin, literal-gwmin, random, round-robin, proportional-fair) and
the online FL-state-aware policies (update-aware, age-fair,
matching-pursuit), which are selected round by round inside the training
loop from the previous rounds' update norms / ages.

``--uplink ota`` switches the round aggregate from digital
decode-and-average to the analog over-the-air superposition
(``repro.core.ota``): scheduled devices transmit simultaneously with
truncated-channel-inversion scaling and the PS receives one noisy sum —
DoReFa quantization and top-k never apply, so the driver forces
``compression="none"``.  ``--ota-noise`` sets the receiver noise std
(0 = the exact weighted aggregate) and ``--ota-threshold`` the
inversion truncation (devices below that fraction of the round's best
channel sit out).  ``--power ota-align`` reports the matching
channel-inversion control-plane powers; ``--scheduler
matching-pursuit`` is the OTA-aware online policy (greedy residual
aggregation-error decrease).  Example:

    PYTHONPATH=src python examples/fl_noma_mnist.py --fast \
        --uplink ota --ota-noise 1e-9 --scheduler matching-pursuit

``--engine`` picks the round-body engine (``FLConfig.fl_engine``):
``batched`` (default here) runs each round as one jitted dispatch over a
device-resident ClientBank — several times faster per round (see
BENCH_fl.json) and equal to the legacy loop to f32 tolerance;
``legacy`` is the per-device oracle loop.  ``--pallas-agg`` sets
``FLConfig.use_pallas``: the batched engine then aggregates through the
fused dequant+aggregate Pallas kernel instead of the XLA einsum.

``--horizon scan`` (``FLConfig.horizon``) runs the whole horizon as ONE
``lax.scan`` device program instead of dispatching round by round —
identical schedules/bits/rates/times, bit-identical accuracies
(tests/test_fl_scan.py).  Online policies run under the scan too, via the
traced selection protocol (tests/test_policy_scan.py; BENCH_policy.json
tracks the speedup) — selection, power allocation and budget pricing all
execute inside the scan body, e.g.::

    PYTHONPATH=src python examples/fl_noma_mnist.py --fast \
        --horizon scan --scheduler update-aware

(the driver then defaults ``--power`` to ``max``: MAPEL's host-iterative
polyblock search cannot run inside the traced round body).
``--seeds N`` additionally sweeps
N independent seeds (model init + channel draws + schedule each) through
``fl.run_horizon_vmapped`` — one vmapped program for the whole sweep —
and reports the mean/std final accuracy; it implies ``--horizon scan``.
Multi-cell grids with the cell axis sharded over a device mesh live in
``fl.run_cell_sweep`` (BENCH_cells.json tracks the sweep speedup).

Model and compression flags (the model-agnostic payload path):

``--model`` picks the FL payload (``FLConfig.model``, resolved through
``repro.models.fl_models``): ``lenet`` (default — the paper's
LeNet-300-100 on MNIST-like images, bit-identical to the historical
hardcoded path), ``tiny-transformer`` / ``tiny-transformer-1m`` (dense
next-token transformers; the ``-1m`` variant is the >=10^6-param
transformer-class payload), or any ``repro.configs`` arch id such as
``qwen2_0_5b:smoke``.  Token models train on a synthetic next-token
corpus (``repro.data.tokens.make_token_dataset``) partitioned with the
same Dirichlet non-iid machinery as the image path.

``--topk`` (< 1.0) turns on top-k sparsification before DoReFa: each
client keeps only the affordable top fraction of update coordinates
under its §IV bit budget (``compression.topk_plan``) and the logged
compression ratios become the honest sparse on-air ratios I / S_k.
Requires the batched engine or the scan horizon (the legacy oracle loop
stays dense).  Example — a transformer-class payload with 1% top-k over
the scanned horizon:

    PYTHONPATH=src python examples/fl_noma_mnist.py --fast \
        --model tiny-transformer-1m --topk 0.01 --horizon scan

Takes ~10-20 min at full scale on this CPU (legacy engine; the batched
engine cuts the round-loop time severalfold); --fast runs M=60, T=10.
"""
import argparse
import contextlib
import os
import sys

import numpy as np

from repro.config import FLConfig
from repro.core import channel, fl, ota, scheduling
from repro.data import dirichlet_partition, make_mnist_like
from repro.data.tokens import make_token_dataset
from repro.models.fl_models import get_fl_model


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--scheduler", default="lazy-gwmin",
                    choices=scheduling.available_policies())
    ap.add_argument("--power", default=None,
                    help="power mode (default mapel; ota uplink defaults "
                         "to max — MAPEL optimizes SIC decode rates the "
                         "analog sum never performs — as does an online "
                         "scheduler under --horizon scan, whose traced "
                         "round body cannot run the host-iterative "
                         "polyblock search)")
    ap.add_argument("--uplink", default="noma", choices=ota.UPLINK_MODES)
    ap.add_argument("--ota-noise", type=float, default=0.0,
                    help="OTA receiver noise std (uplink=ota; 0 = exact "
                         "weighted aggregate)")
    ap.add_argument("--ota-threshold", type=float, default=0.0,
                    help="truncated channel inversion: devices below this "
                         "fraction of the round's best gain sit out")
    ap.add_argument("--rounds", type=int, default=None)
    ap.add_argument("--engine", default="batched", choices=["legacy", "batched"])
    ap.add_argument("--pallas-agg", action="store_true",
                    help="batched engine: aggregate via the Pallas kernel")
    ap.add_argument("--horizon", default="per-round",
                    choices=["per-round", "scan"],
                    help="scan: whole horizon as one lax.scan program "
                         "(precomputed schedules, and online policies via "
                         "the traced selection protocol)")
    ap.add_argument("--seeds", type=int, default=None,
                    help="sweep N seeds through one vmapped scan program "
                         "(implies --horizon scan)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--model", default="lenet",
                    help="FL payload (FLConfig.model): lenet, "
                         "tiny-transformer, tiny-transformer-1m, or a "
                         "repro.configs arch id ('<id>' / '<id>:smoke')")
    ap.add_argument("--topk", type=float, default=1.0,
                    help="top-k sparsification cap before DoReFa "
                         "(fraction of coordinates kept; 1.0 = dense; "
                         "batched engine / scan horizon only)")
    ap.add_argument("--sanitize-nans", action="store_true",
                    help="run under the flcheck NaN sanitizer "
                         "(jax_debug_nans): a NaN anywhere in the FL math "
                         "raises FloatingPointError at the source instead "
                         "of poisoning the accuracy curve; slow, debug only")
    args = ap.parse_args()
    if args.seeds is not None:
        args.horizon = "scan"
    if args.power is None:
        online_scan = (args.horizon == "scan"
                       and scheduling.policy_is_online(args.scheduler))
        args.power = ("max" if args.uplink == "ota" or online_scan
                      else "mapel")

    m = 60 if args.fast else 300              # paper: M = 300
    t = args.rounds or (10 if args.fast else 35)  # paper: T = 35

    model = get_fl_model(args.model)
    if model.kind == "tokens":
        # synthetic next-token corpus, Dirichlet-partitioned by the rows'
        # pseudo-class so the non-iid shard machinery matches the image path
        ds = make_token_dataset(
            vocab_size=model.cfg.vocab_size,
            num_samples=4000 if args.fast else 12_000,
            seq_len=16, seed=args.seed)
        part_labels = ds.class_train
    else:
        ds = make_mnist_like(num_samples=4000 if args.fast else 12_000,
                             seed=args.seed)
        part_labels = ds.y_train
    cell = channel.CellConfig(num_devices=m)   # paper §IV cell parameters
    shards = dirichlet_partition(part_labels, m, seed=args.seed)
    # the analog sum never decodes per-device payloads: DoReFa / top-k
    # cannot apply under OTA (FLConfig rejects the combo with the reason)
    compression = "none" if args.uplink == "ota" else "adaptive"
    topk = 1.0 if args.uplink == "ota" else args.topk
    cfg = FLConfig(num_devices=m, group_size=3, num_rounds=t,
                   learning_rate=0.01, batch_size=10,   # Table I
                   scheduler=args.scheduler, power_mode=args.power,
                   compression=compression, fl_engine=args.engine,
                   use_pallas=args.pallas_agg, horizon=args.horizon,
                   model=args.model, topk=topk, uplink=args.uplink,
                   ota_noise=args.ota_noise, ota_threshold=args.ota_threshold,
                   seed=args.seed)

    online = scheduling.get_policy(args.scheduler).online
    print(f"M={m} K=3 T={t} scheduler={args.scheduler} power={args.power} "
          f"uplink={args.uplink} engine={args.engine} "
          f"horizon={args.horizon} model={args.model} "
          f"{'topk=' + format(args.topk, '.2f') + ' ' if args.topk < 1 else ''}"
          f"mode={'online (live)' if online else 'precomputed'}")

    if args.sanitize_nans:
        # tools/ sits next to src/ at the repo root, not on the examples/
        # script path argparse launches from
        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        from tools.flcheck.sanitizers import nan_guard

        guard = nan_guard()
    else:
        guard = contextlib.nullcontext()

    with guard:
        if args.seeds is not None:
            sweep = fl.run_horizon_vmapped(
                ds, shards, cell, cfg,
                seeds=range(args.seed, args.seed + args.seeds),
                uplink=args.uplink)
            finals = np.array([r.accuracies()[-1] for r in sweep])
            for i, r in enumerate(sweep):
                print(f"seed {args.seed + i}: final acc "
                      f"{r.accuracies()[-1]:.3f} "
                      f"sim time {r.times()[-1]:6.1f}s")
            print(f"\n{args.seeds} seeds: final acc {finals.mean():.3f} "
                  f"+/- {finals.std():.3f}")
            return

        res = fl.run_federated_learning(
            ds, shards, cell, cfg, uplink=args.uplink,
            progress=lambda log: print(
                f"round {log.round:3d} acc={log.test_accuracy:.3f} "
                f"bits={log.bits.tolist()} t={log.wall_time_s:6.1f}s"))
    accs = res.accuracies()
    print(f"\nfinal acc {accs[-1]:.3f}; mean-last-5 "
          f"{np.mean(accs[-5:]):.3f}; total sim time {res.times()[-1]:.1f}s")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
