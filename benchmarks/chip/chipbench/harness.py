"""The on-chip benchmark harness: one run of one cell.

    python benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

A run builds the cell's world from ``--seed``, warms up the cell's entry
point (one call: every program of the cell's shapes compiles or loads from
the compile cache), then calls it back to back, a closed loop like a
researcher's sweep script, until ``--seconds`` have passed.  Each call ends
in ``block_until_ready`` on its results.  After the window the program's
results are compared with the plain reference of the cell's configuration
(``compare``), and the last line of standard output is one JSON object.

Everything a cell is made of is data found by name: ``BENCHMARK.json``
names its configuration and traffic, ``configs/<config>.json`` holds the
deployment, ``traffic/<traffic>.json`` the entry point and its settings,
``cells/<workload>.json`` the comparison's limits, ``entries/<entry>.py``
drives the program, ``worlds/<data.kind>.py`` makes the data,
``references/<reference>.py`` is the plain reference (and counts the
model's FLOPs and parameters), and ``metrics/<metric>.py`` reads one
per-layer metric.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import os
import pathlib
import shutil
import statistics
import sys
import tempfile
import time
import types

import numpy as np

from chipbench import compare, flops, world as world_lib, xtrace

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class NoChip(RuntimeError):
    pass


def log(*parts):
    print(*parts, file=sys.stderr, flush=True)


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path):
    name = "chipbench_" + "_".join(path.relative_to(BENCH_DIR).with_suffix(
        "").parts).replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    spec: dict           # cells/<name>.json: what is compared, and limits
    end_to_end: list     # BENCHMARK.json metrics this cell reports
    per_layer: list


def _reports(metric, name):
    return "workloads" not in metric or name in metric["workloads"]


def load_cell(name):
    bench = load_json(ROOT / "BENCHMARK.json")
    found = [w for w in bench["workloads"] if w["name"] == name]
    if not found:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = found[0]
    conf = [c for c in bench["configs"] if c["name"] == w["config"]][0]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=load_json(ROOT / conf["file"]),
        traffic=load_json(BENCH_DIR / "traffic" / f"{w['traffic']}.json"),
        spec=load_json(BENCH_DIR / "cells" / f"{name}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)],
    )


class CompileCounter:
    """XLA programs compiled or loaded from the cache while ``active``."""

    def __init__(self):
        import jax

        self.active = False
        self.requests = self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kwargs):
        if self.active and event == BACKEND_COMPILE_EVENT:
            self.requests += 1

    def _event(self, event, **kwargs):
        if self.active and event == CACHE_HIT_EVENT:
            self.hits += 1

    def close(self):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)


def _chips(jax, chips, require_tpu):
    devices = jax.devices()
    if require_tpu:
        if devices[0].platform != "tpu":
            raise NoChip(f"no TPU found (JAX platform "
                         f"{devices[0].platform!r}); the benchmark runs on "
                         f"the chip only")
        if len(devices) < chips:
            raise NoChip(f"the cell needs {chips} TPU chips; JAX sees "
                         f"{len(devices)}")
    return devices[:chips]


def _peak(kind):
    table = load_json(BENCH_DIR / "peaks.json")["kinds"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


def _p90(values):
    if len(values) < 2:
        return float(values[0])
    return float(statistics.quantiles(values, n=10, method="inclusive")[8])


def checked(cell, calls, per_call, seed):
    """Which horizons the check compares, drawn from the run's seed: the
    sets (of those the window called) and the instances within them."""
    rng = np.random.default_rng(seed)
    sets = sorted(set(calls))
    spec = cell.spec["check"]
    sets = rng.choice(sets, size=min(spec.get("sets", 1), len(sets)),
                      replace=False)
    n = min(spec["instances"], per_call)
    return sorted((int(j), int(i)) for j in sets
                  for i in rng.choice(per_call, size=n, replace=False))


def reference(cell):
    return load_module(BENCH_DIR / "references"
                       / f"{cell.config['reference']}.py")


def run_reference(cell, world, fl, instance_seed, program_record, **kw):
    """The reference's horizon for one instance; a cell whose policy
    selects online has the reference follow the program's groups (see
    ``references``)."""
    follow = (program_record["devices"] if cell.spec["check"].get("follow")
              else None)
    return reference(cell).run_instance(world, fl, cell.config["cell"],
                                        instance_seed, follow=follow, **kw)


def _check(cell, world, entry, calls, records, seed):
    """Compare the program's horizons with the reference's; returns
    ``(correct, failed_calls, checks)``.  ``calls[n]`` is the set that
    window call n ran, ``records[n]`` its horizons."""
    pool = len(entry.sets)
    sets = [j % pool for j in calls]
    pairs = checked(cell, sets, len(entry.sets[0]), seed)
    first = {j: records[sets.index(j)] for j, _ in pairs}
    t0 = time.perf_counter()
    refs = {(j, i): run_reference(cell, world, entry.fl, entry.sets[j][i],
                                  first[j][i]) for j, i in pairs}
    log(f"reference: {len(pairs)} horizons in "
        f"{time.perf_counter() - t0!r} s")
    readings, failed = [], 0
    for j, call in zip(sets, records):
        per_call = [compare.numbers(call[i], refs[(jj, i)])
                    for jj, i in pairs if jj == j]
        if not per_call:
            continue
        readings.extend(per_call)
        ok, _ = compare.verdict(compare.worst(per_call), cell.spec["limits"])
        failed += not ok
    for (j, i), ref in refs.items():
        log(f"reference instance seed {entry.sets[j][i]}: final accuracy "
            f"{float(ref['accs'][-1])!r}")
    correct, checks = compare.verdict(compare.worst(readings),
                                      cell.spec["limits"])
    return correct, failed, checks


def run(cell, *, seed, seconds, trace, require_tpu=True, t0=None):
    """One run of ``cell``; returns the result object (the last line)."""
    t0 = time.perf_counter() if t0 is None else t0
    import jax

    log(f"set-up: jax imported at {time.perf_counter() - t0!r} s")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = _chips(jax, cell.chips, require_tpu)
    kind = devices[0].device_kind
    log(f"device: {devices[0].platform} {kind!r} x{len(devices)}")
    log(f"set-up: chip ready at {time.perf_counter() - t0!r} s")

    world = world_lib.build_world(cell.config, seed)
    entry_mod = load_module(BENCH_DIR / "entries"
                            / f"{cell.traffic['entry']}.py")
    entry = entry_mod.Entry(world, cell.config, cell.traffic, seed)
    log(f"set-up: world and entry at {time.perf_counter() - t0!r} s")
    log(f"instance seeds: {entry.sets}")
    with jax.profiler.TraceAnnotation("bench.warmup"):
        entry.call(0)
    setup_s = time.perf_counter() - t0
    log(f"setup_s {setup_s!r}")

    counter = CompileCounter()
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    if trace:
        # host spans (TraceAnnotation) only: the Python tracer would record
        # every Python call of the program and slow its host work severalfold
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        options.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    kept, seconds_per_call = [], []
    counter.active = True
    w0 = time.perf_counter()
    while True:
        c0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.call"):
            kept.append(entry.call(len(kept) + 1))
        c1 = time.perf_counter()
        seconds_per_call.append(c1 - c0)
        if c1 - w0 >= seconds:
            break
    window_s = c1 - w0
    counter.close()
    if trace:
        jax.profiler.stop_trace()
    log(f"calls in window: {len(kept)}; in-window XLA compiles or cache "
        f"loads: {counter.requests} ({counter.hits} from the cache)")

    stats = [d.memory_stats() or {} for d in devices]
    peak_bytes = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    from chipbench import program

    calls = list(range(1, len(kept) + 1))
    records = [[program.record(r, s) for r, s in
                zip(call, entry.sets[j % len(entry.sets)])]
               for j, call in zip(calls, kept)]
    del kept
    horizons = sum(len(call) for call in records)
    rounds = cell.config["fl"]["num_rounds"]
    train_flops, eval_flops = reference(cell).sample_flops(cell.config)
    done_flops = sum(
        flops.horizon_flops(
            rec["devices"], world.sizes,
            epochs=cell.config["fl"]["local_epochs"],
            test_samples=len(world.dataset.y_test),
            train_flops=train_flops, eval_flops=eval_flops)
        for call in records for rec in call)

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    result = {"correct": False, "attempted": len(records), "failed": 0,
              "metrics": {}, "device": device}
    if trace:
        tr = xtrace.load(xtrace.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        lo, hi = xtrace.window(tr)
        busy = xtrace.busy_ns(tr, lo, hi)
        ctx = types.SimpleNamespace(
            trace=tr, lo=lo, hi=hi, window_s=(hi - lo) * 1e-9, busy_ns=busy,
            chips=len(devices), flops=done_flops, instances=horizons,
            instance_rounds=horizons * rounds, peak=_peak(kind))
        device["busy_s"] = sum(busy) / len(devices) * 1e-9
        device["window_s"] = (hi - lo) * 1e-9
        for m in cell.per_layer:
            value = load_module(BENCH_DIR / "metrics"
                                / f"{m['name']}.py").read(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        result["breakdown"] = {
            "device_ops": xtrace.top_ops(tr, lo, hi),
            "idle_gaps": xtrace.idle_gaps(tr, lo, hi),
        }
    else:
        e2e = {"rounds_per_s": horizons * rounds / window_s,
               "horizon_p90_s": _p90(seconds_per_call),
               "setup_s": setup_s}
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                            "unit": m["unit"]}
    log(f"seconds per call: {seconds_per_call!r}")
    log(f"seconds per call: median {statistics.median(seconds_per_call)!r}, "
        f"max {max(seconds_per_call)!r}")

    with jax.profiler.TraceAnnotation("bench.reference"):
        correct, failed, checks = _check(cell, world, entry, calls, records,
                                         seed)
    result["correct"] = bool(correct)
    result["failed"] = failed
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    return result


def main(argv=None, t0=None):
    t0 = time.perf_counter() if t0 is None else t0
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        log(f"the program is not in this checkout ({ROOT / 'src'} missing)")
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(ROOT / ".jax_cache"))
    cell = load_cell(args.workload)
    try:
        result = run(cell, seed=args.seed, seconds=args.seconds,
                     trace=bool(args.trace), t0=t0)
    except NoChip as e:
        log(str(e))
        return 1
    print(json.dumps(result), flush=True)
    return 0
