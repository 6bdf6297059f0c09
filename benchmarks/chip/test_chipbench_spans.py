"""The program's host spans, recorded by the real profiler on the CPU and
read back by ``chipbench.hostspans``: the tiny cells go through the three
paths the benchmark's cells use (the scanned horizon with lazy GWMIN and
MAPEL, the online update-aware scan, the vmapped seed sweep, here two
seeds wide), and a synthetic trace pins the reduction."""
import types

import pytest

import breakdown
from chipbench import hostspans, testing, xtrace

E = xtrace.Event
CELLS = ("paper-noma.mapel-gwmin", "paper-noma.online-update-aware",
         "ota.seed-sweep8")


@pytest.fixture(scope="module")
def runs():
    out = {}
    for name in CELLS:
        cell = testing.tiny_cell(name)
        if cell.traffic["per_call"] > 1:
            cell.traffic = {**cell.traffic, "per_call": 2}
        out[name] = (cell, breakdown.traced_calls(cell, 2**31 + 9, 0.0,
                                                  require_tpu=False))
    return out


def _within(inner, outer):
    return outer.start_ns <= inner.start_ns and inner.end_ns <= outer.end_ns


def _parent(span, spans):
    """The innermost other span that holds ``span``."""
    holders = [s for s in spans if s is not span and _within(span, s)]
    return min(holders, key=lambda s: s.dur_ns) if holders else None


@pytest.mark.parametrize("name", CELLS)
def test_span_tree(runs, name):
    cell, (trace, seconds, instances, _) = runs[name]
    lo, hi = xtrace.window(trace)
    spans = [s for s in trace.spans if s.start_ns >= lo and s.end_ns <= hi]
    named = {n: [s for s in spans if s.name == n]
             for n in ("bench.call", "fl.horizon", "fl.plan", "fl.schedule",
                       "fl.power", "fl.bank", "fl.dispatch", "fl.sync",
                       "fl.replay")}
    assert len(seconds) == 1 and len(named["bench.call"]) == 1
    assert instances == cell.traffic["per_call"]
    assert [_parent(s, spans).name for s in named["fl.horizon"]] == [
        "bench.call"]
    assert len(named["fl.plan"]) == instances
    assert len(named["fl.schedule"]) == instances
    for s in named["fl.plan"] + named["fl.bank"] + named["fl.dispatch"]:
        assert _parent(s, spans).name == "fl.horizon"
    for s in named["fl.schedule"]:
        assert _parent(s, spans).name == "fl.plan"
    # a precomputed schedule allocates its powers (MAPEL or max) once, and
    # syncs once for GWMIN's device program; the scan's sync is the root's
    gwmin = cell.traffic["fl"]["scheduler"] == "lazy-gwmin"
    assert len(named["fl.power"]) == (instances if gwmin else 0)
    for s in named["fl.power"]:
        assert _parent(s, spans).name == "fl.schedule"
    assert sorted(_parent(s, spans).name for s in named["fl.sync"]) == (
        ["fl.horizon"] + ["fl.schedule"] * (instances if gwmin else 0))
    assert len(named["fl.replay"]) == 1


@pytest.mark.parametrize("name", CELLS)
def test_readers_find_each_host_layer(runs, name):
    cell, traced = runs[name]
    result = breakdown.breakdown(cell, *traced)
    online = cell.traffic["fl"]["scheduler"] == "update-aware"
    for span, ms in result["host_ms_per_instance"].items():
        if span == "fl.power" and online:
            assert ms is None
        else:
            assert ms > 0, span
    assert 0.0 <= result["untraced_share"] < 0.2
    assert result["covered_ms_per_call"] <= result["median_call_ms"]
    assert result["counters"]["bank.bytes_uploaded"] > 0
    if cell.traffic["fl"]["power_mode"] == "mapel":
        assert result["mapel_iters_per_group"] > 0
        assert result["counters"]["power.mapel_groups"] == (
            cell.config["fl"]["num_rounds"] * result["instances"])
    else:
        assert result["mapel_iters_per_group"] is None


def test_mapel_metric_reads_the_program_counters(runs):
    from repro.utils import spans

    from chipbench import harness

    read = harness.load_module(
        harness.BENCH_DIR / "metrics" / "mapel_iters_per_group.py").read
    assert read(None) == hostspans.iters_per_group(spans.counts()) > 0


def _synthetic():
    # call [0, 1000]: horizon [10, 990] holds plan [20, 500] (holding
    # schedule [100, 400], itself holding power [150, 350]) and sync
    # [600, 700]; call [1000, 1600]: horizon [1010, 1590] holds plan
    # [1100, 1500]
    return xtrace.Trace(
        ops=[], modules=[],
        spans=[E("bench.call", 0, 1000), E("fl.horizon", 10, 980),
               E("fl.plan", 20, 480), E("fl.schedule", 100, 300),
               E("fl.power", 150, 200), E("fl.sync", 600, 100),
               E("bench.call", 1000, 600), E("fl.horizon", 1010, 580),
               E("fl.plan", 1100, 400)],
        chips=0)


def test_span_self_time_nests_and_clips():
    tr = _synthetic()
    lo, hi = xtrace.window(tr)
    assert (lo, hi) == (0, 1600)
    assert hostspans.span_self_ns(tr, "fl.power", lo, hi) == 200
    assert hostspans.span_self_ns(tr, "fl.schedule", lo, hi) == 100
    assert hostspans.span_self_ns(tr, "fl.plan", lo, hi) == (480 - 300) + 400
    assert hostspans.span_self_ns(tr, "fl.horizon", lo, hi) == (
        980 - 480 - 100) + (580 - 400)
    # clipped: the second plan to [1100, 1200]; then the first plan and
    # its schedule to [20, 300] and [100, 300]
    assert hostspans.span_self_ns(tr, "fl.plan", 0, 1200) == 180 + 100
    assert hostspans.span_self_ns(tr, "fl.plan", 0, 300) == 280 - 200
    assert hostspans.host_ms(tr, "fl.sync", lo, hi, 2) == pytest.approx(
        100e-6 / 2)
    # covered: [20, 500] + [600, 700] + [1100, 1500] of 1600
    assert hostspans.untraced_share(tr, lo, hi) == pytest.approx(
        1 - 980 / 1600)


def test_readers_read_nothing_without_the_program_spans():
    tr = _synthetic()
    tr.spans = [s for s in tr.spans if s.name.startswith("bench.")]
    for name in hostspans.PARTS:
        assert hostspans.host_ms(tr, name, 0, 1200, 2) is None
    assert hostspans.untraced_share(tr, 0, 1200) == 1.0
    assert hostspans.iters_per_group({}) is None
    assert hostspans.iters_per_group({"bank.bytes_uploaded": 8}) is None
    assert hostspans.untraced_share(types.SimpleNamespace(spans=[]), 0,
                                    1200) is None
