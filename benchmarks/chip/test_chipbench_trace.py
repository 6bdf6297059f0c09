"""The reduction from a profiler trace to the per-layer metrics."""
import json
import pathlib
import types

import pytest

from chipbench import harness, xtrace

RECORDED = pathlib.Path(__file__).parent / "testdata" / "trace_mapel_gwmin.json"
E = xtrace.Event


def _synthetic():
    # two calls on the host, device ops that overlap, one module each
    return xtrace.Trace(
        ops=[E("fusion.1", 100, 50, 0, "jit_run_horizon"),
             E("fusion.2", 120, 50, 0, "jit_run_horizon"),
             E("while.3", 400, 100, 0, "jit__fused_single"),
             E("copy.4", 900, 200, 0, "jit_other")],
        modules=[E("jit_run_horizon(1)", 100, 70, 0),
                 E("jit__fused_single", 400, 100, 0),
                 E("jit_run_horizon_vmapped", 950, 10, 0),
                 E("jit_run_horizonx", 960, 10, 0)],
        spans=[E("bench.call", 0, 500), E("bench.call", 600, 400),
               E("bench.reference", 2000, 10)],
        chips=1)


def _read(name, ctx):
    return harness.load_module(
        harness.BENCH_DIR / "metrics" / f"{name}.py").read(ctx)


def test_window_busy_and_gaps():
    tr = _synthetic()
    lo, hi = xtrace.window(tr)
    assert (lo, hi) == (0, 1000)
    # [100, 170] + [400, 500] + [900, 1000] (clipped at the window's end)
    assert xtrace.busy_ns(tr, lo, hi) == [270]
    gaps = xtrace.idle_gaps(tr, lo, hi)
    assert gaps[0][0] == "bench.call"               # 500..900
    assert gaps[0][1] == pytest.approx(400e-9)
    assert [g[1] for g in gaps] == sorted([g[1] for g in gaps], reverse=True)
    assert sum(g[1] for g in gaps) == pytest.approx(730e-9)


def test_modules_match_by_name_and_clip():
    tr = _synthetic()
    assert xtrace.matches("jit_run_horizon(1)", ("run_horizon",))
    assert not xtrace.matches("jit_run_horizonx", ("run_horizon",))
    assert xtrace.module_ns(tr, ("run_horizon", "run_horizon_vmapped"),
                            0, 1000) == 80
    top = xtrace.top_ops(tr, 0, 1000)
    assert {t[0] for t in top[:2]} == {"jit_other/copy.4",
                                       "jit__fused_single/while.3"}
    assert top[2] == ["jit_run_horizon/fusion.1", pytest.approx(50e-9)]


def test_metrics_read_the_reduced_trace():
    tr = _synthetic()
    ctx = types.SimpleNamespace(
        trace=tr, lo=0, hi=1000, window_s=1e-6,
        busy_ns=xtrace.busy_ns(tr, 0, 1000), chips=1, flops=1.97e6,
        instances=2, instance_rounds=8,
        peak={"bf16_flops_per_s": 197e12})
    assert _read("device_idle_share", ctx) == pytest.approx(73.0)
    assert _read("round_body_device_ms", ctx) == pytest.approx(80e-6 / 8)
    assert _read("scheduler_device_ms", ctx) == pytest.approx(100e-6 / 2)
    assert _read("mfu", ctx) == pytest.approx(1.0)
    ctx.trace = xtrace.Trace([], [], tr.spans, 0)
    ctx.busy_ns = []
    for name in ("device_idle_share", "round_body_device_ms",
                 "scheduler_device_ms"):
        assert _read(name, ctx) is None


def test_round_body_reads_the_sharded_sweep():
    """The cell sweep's scan runs as ``jit_fn`` on every chip at once: the
    device time of all four counts, per instance-round."""
    tr = xtrace.Trace(
        ops=[E("fusion.1", 100, 40, c, "jit_fn") for c in range(4)],
        modules=[E("jit_fn(7)", 100, 40, c) for c in range(4)],
        spans=[E("bench.call", 0, 500)], chips=4)
    ctx = types.SimpleNamespace(trace=tr, lo=0, hi=500, instance_rounds=16)
    assert _read("round_body_device_ms", ctx) == pytest.approx(
        4 * 40e-6 / 16)


def test_json_round_trip():
    tr = _synthetic()
    back = xtrace.Trace.from_json(json.loads(json.dumps(tr.to_json())))
    assert back == tr


def test_recorded_chip_trace():
    """The first call of a traced ``paper-noma.mapel-gwmin`` run on one
    TPU v5e (ops shorter than 2 us dropped, times from the call's start):
    the numbers the reduction gave when it was recorded."""
    tr = xtrace.Trace.from_json(json.loads(RECORDED.read_text()))
    lo, hi = xtrace.window(tr)
    assert (lo, hi) == (0, 1274826477)
    assert xtrace.busy_ns(tr, lo, hi) == [14143475]
    assert xtrace.module_ns(tr, ("run_horizon",), lo, hi) == 3975837
    assert xtrace.module_ns(tr, ("_fused_single",), lo, hi) == 10165581
    assert xtrace.top_ops(tr, lo, hi)[0] == ["while.10",
                                             pytest.approx(0.010158467)]
    gap = xtrace.idle_gaps(tr, lo, hi)[0]
    assert gap == ["bench.call", pytest.approx(1.14416391)]
    assert all(" = " not in e.name for e in tr.ops)
    ctx = types.SimpleNamespace(
        trace=tr, lo=lo, hi=hi, window_s=(hi - lo) * 1e-9,
        busy_ns=xtrace.busy_ns(tr, lo, hi), chips=1, flops=0.0,
        instances=1, instance_rounds=35, peak={"bf16_flops_per_s": 197e12})
    assert _read("device_idle_share", ctx) == pytest.approx(
        100 * (1 - 14143475 / 1274826477))
    assert _read("scheduler_device_ms", ctx) == pytest.approx(10.165581)
    assert _read("round_body_device_ms", ctx) == pytest.approx(
        3.975837 / 35)
    assert _read("mfu", ctx) is None
