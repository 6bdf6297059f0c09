"""Readings that set a cell's limits: the program against the plain
reference on many seeds, the control (the reference computed in bfloat16,
the precision below the configuration's float32, put in the program's
place) against the same reference, and the program with each of the
planted faults of ``chipbench.testing.FAULTS`` against it, all at the
cell's own size.  All seeds run in this one process; one JSON line per
compared horizon goes to standard output and to ``--out``.

    python benchmarks/chip/calibrate.py --workload paper-noma.mapel-gwmin \
        --seeds 1,2,3 --control-seeds 1,2,3 --fault-seeds 1,2,3 \
        --out chiprun_out/calib.jsonl
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import harness  # noqa: E402


def _seeds(text):
    return [int(s) for s in text.split(",") if s]


def _faulted(cell, entry, seeds_0):
    """The program's records of call 0 with each planted fault in turn."""
    import jax

    from chipbench import program, testing

    out = {}
    for name, plant in sorted(testing.FAULTS.items()):
        jax.clear_caches()
        with plant(cell):
            out[name] = [program.record(r, s)
                         for r, s in zip(entry.call(0), seeds_0)]
    jax.clear_caches()
    return out


def calibrate(cell, seeds, control_seeds, fault_seeds=(), *, calls=1,
              require_tpu=True):
    import jax
    import jax.numpy as jnp

    from chipbench import compare, program, world as world_lib

    harness._chips(jax, cell.chips, require_tpu)
    ref = harness.reference(cell)
    entry_mod = harness.load_module(harness.BENCH_DIR / "entries"
                                    / f"{cell.traffic['entry']}.py")
    for seed in sorted(set(seeds) | set(control_seeds) | set(fault_seeds)):
        t0 = time.perf_counter()
        world = world_lib.build_world(cell.config, seed)
        entry = entry_mod.Entry(world, cell.config, cell.traffic, seed)
        seeds_0 = entry.sets[0]
        got = [program.record(r, s) for r, s in zip(entry.call(0), seeds_0)]
        call_s = []
        for n in range(1, calls):
            c0 = time.perf_counter()
            entry.call(n)
            call_s.append(time.perf_counter() - c0)
        faulted = (_faulted(cell, entry, seeds_0) if seed in fault_seeds
                   else {})
        for _, i in harness.checked(cell, [0], len(seeds_0), seed):
            row = {"workload": cell.name, "seed": seed,
                   "instance_seed": seeds_0[i]}
            if seed in seeds:
                want = harness.run_reference(cell, world, entry.fl,
                                             seeds_0[i], got[i])
                row["final_acc"] = float(want["accs"][-1])
                row["program"] = compare.numbers(got[i], want)
            if seed in control_seeds:
                ctl = ref.run_instance(world, entry.fl, cell.config["cell"],
                                       seeds_0[i], dtype=jnp.bfloat16)
                want = harness.run_reference(cell, world, entry.fl,
                                             seeds_0[i], ctl)
                row["control"] = compare.numbers(ctl, want)
            if faulted:
                row["faults"] = {
                    name: compare.numbers(recs[i], harness.run_reference(
                        cell, world, entry.fl, seeds_0[i], recs[i]))
                    for name, recs in faulted.items()}
            row["call_s"] = call_s
            row["seconds"] = time.perf_counter() - t0
            yield row


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, default=[])
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    ap.add_argument("--fault-seeds", type=_seeds, default=[])
    ap.add_argument("--calls", type=int, default=1,
                    help="calls per seed; the seconds of all but the first "
                         "are reported as call_s")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    if not (harness.ROOT / "src" / "repro").is_dir():
        print("the program is not in this checkout", file=sys.stderr)
        return 2
    sys.path.insert(0, str(harness.ROOT / "src"))
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(harness.ROOT / ".jax_cache"))
    cell = harness.load_cell(args.workload)
    out = open(args.out, "a") if args.out else None
    try:
        for row in calibrate(cell, args.seeds, args.control_seeds,
                             args.fault_seeds, calls=args.calls):
            line = json.dumps(row)
            print(line, flush=True)
            if out:
                out.write(line + "\n")
                out.flush()
    except harness.NoChip as e:
        print(str(e), file=sys.stderr)
        return 1
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
