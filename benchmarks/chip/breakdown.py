"""Where a cell's calls spend their host time, read from the program's own
spans.  A run is ``run.py --trace 1``'s (the same world, entry point,
warm-up and closed loop of calls under the profiler), reduced with
``chipbench.hostspans``: each host layer's self time per instance (plan,
schedule, power, bank, dispatch, sync, replay), the share of call time no
program span covers, the longest idle gaps named by the innermost span
over them, and the program's counters in the window.  There is no check
against the reference: that is ``run.py``'s.  One cell per process, as
``run.py`` runs it (a cell run after another in the same process read its
bank build four times faster on the chip); one JSON line to standard
output and to ``--out``.

    python benchmarks/chip/breakdown.py --workload paper-noma.mapel-gwmin \
        --seed 7 --seconds 20 --out breakdown.jsonl
"""
import argparse
import json
import os
import shutil
import statistics
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import harness, hostspans, xtrace  # noqa: E402


def _counts():
    try:
        from repro.utils import spans
    except ImportError:         # a program without counters
        return {}
    return spans.counts()


def traced_calls(cell, seed, seconds, *, require_tpu=True):
    """Warm up, then call the cell's entry point under the profiler for
    ``seconds``; returns ``(trace, seconds_per_call, instances,
    counters)``, the counters being what the window added."""
    import jax

    from chipbench import world as world_lib

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    harness._chips(jax, cell.chips, require_tpu)
    world = world_lib.build_world(cell.config, seed)
    entry = harness.load_module(
        harness.BENCH_DIR / "entries" / f"{cell.traffic['entry']}.py"
    ).Entry(world, cell.config, cell.traffic, seed)
    entry.call(0)
    trace_dir = tempfile.mkdtemp(prefix="chipbench-breakdown-")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    options.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    before = _counts()
    seconds_per_call, instances = [], 0
    w0 = time.perf_counter()
    while True:
        c0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(xtrace.CALL_SPAN):
            instances += len(entry.call(len(seconds_per_call) + 1))
        c1 = time.perf_counter()
        seconds_per_call.append(c1 - c0)
        if c1 - w0 >= seconds:
            break
    after = _counts()
    jax.profiler.stop_trace()
    trace = hostspans.load(xtrace.find_xplane(trace_dir))
    shutil.rmtree(trace_dir, ignore_errors=True)
    counters = {k: v - before.get(k, 0) for k, v in after.items()
                if v != before.get(k, 0)}
    return trace, seconds_per_call, instances, counters


def breakdown(cell, trace, seconds_per_call, instances, counters):
    """The result line of one traced run."""
    lo, hi = xtrace.window(trace)
    calls = len(seconds_per_call)
    host_ms = {name: hostspans.host_ms(trace, name, lo, hi, instances)
               for name in hostspans.PARTS}
    covered = sum(v for v in host_ms.values() if v is not None)
    return {
        "workload": cell.name,
        "calls": calls,
        "instances": instances,
        "rounds_per_s": (instances * cell.config["fl"]["num_rounds"]
                         / sum(seconds_per_call)),
        "median_call_ms": statistics.median(seconds_per_call) * 1e3,
        "host_ms_per_instance": host_ms,
        "covered_ms_per_call": covered * instances / calls,
        "untraced_share": hostspans.untraced_share(trace, lo, hi),
        "idle_gaps": xtrace.idle_gaps(trace, lo, hi),
        "counters": counters,
        "mapel_iters_per_group": hostspans.iters_per_group(counters),
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    if not (harness.ROOT / "src" / "repro").is_dir():
        print("the program is not in this checkout", file=sys.stderr)
        return 2
    sys.path.insert(0, str(harness.ROOT / "src"))
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(harness.ROOT / ".jax_cache"))
    cell = harness.load_cell(args.workload)
    try:
        line = json.dumps(breakdown(cell, *traced_calls(cell, args.seed,
                                                        args.seconds)))
    except harness.NoChip as e:
        print(str(e), file=sys.stderr)
        return 1
    print(line, flush=True)
    if args.out:
        with open(args.out, "a") as out:
            out.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
