"""Host spans and counters (``repro.utils.spans``): each counter the program
keeps equals what the counted call itself reports — MAPEL's from the
iterations and gaps its solution returns, the banks' from their ``nbytes``
— and the module hands out copies, not its own dict."""
import jax
import numpy as np
import pytest

from repro.core import power
from repro.data.client_bank import BucketedClientBank, ClientBank, EvalBank
from repro.utils import spans

NOISE = 1.6e-14
PMAX = 0.01


def _added(before):
    after = spans.counts()
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


@pytest.mark.parametrize("k,max_iter,eps", [(3, 300, 1e-3), (3, 4, 1e-6),
                                            (2, 300, 1e-3), (1, 300, 1e-3),
                                            (4, 300, 1e-3)])
def test_mapel_counters_sum_the_solution(k, max_iter, eps, monkeypatch):
    rng = np.random.default_rng(k * 1000 + max_iter)
    gains = np.abs(rng.normal(1e-6, 5e-7, (6, k))) + 1e-8
    w = rng.dirichlet(np.ones(k), size=6)
    projections = []
    project = power._project_batched

    def counted_project(*args, **kwargs):
        projections.append(1)
        return project(*args, **kwargs)

    monkeypatch.setattr(power, "_project_batched", counted_project)
    before = spans.counts()
    sol = power.mapel_batched(gains, w, PMAX, NOISE, eps=eps,
                              max_iter=max_iter)
    added = _added(before)
    unconverged = int(np.sum((sol.iterations >= max_iter) & (sol.gaps > eps)))
    assert added.get("power.mapel_groups") == 6
    assert added.get("power.mapel_iters", 0) == int(np.sum(sol.iterations))
    assert added.get("power.mapel_unconverged", 0) == unconverged
    # 40 bisection levels (tol 1e-12), _LEVELS_PER_PASS of them a pass
    assert added.get("power.mapel_feasibility_passes", 0) == (
        len(projections) * -(-40 // power._LEVELS_PER_PASS))
    assert added.get("power.mapel_steps", 0) == int(np.max(sol.iterations))
    assert (len(projections) > 0) == (k > 1)
    if max_iter == 4:     # the cut-short solve leaves groups unconverged
        assert unconverged > 0


def test_mapel_counters_skip_an_empty_solve():
    before = spans.counts()
    power.mapel_batched(np.zeros((0, 3)), np.zeros((0, 3)), PMAX, NOISE)
    assert _added(before) == {}


def test_bank_bytes_uploaded_is_each_banks_nbytes(rng):
    x = rng.standard_normal((40, 7)).astype(np.float32)
    y = rng.integers(0, 10, 40).astype(np.int32)
    shards = [np.arange(0, 5), np.arange(5, 25), np.arange(25, 40)]
    for build in (ClientBank.build, BucketedClientBank.build):
        before = spans.counts()
        bank = build(x, y, shards, 4)
        assert _added(before) == {"bank.bytes_uploaded": bank.nbytes}
    before = spans.counts()
    ebank = EvalBank.build(x[:9], y[:9])
    assert ebank.nbytes == 9 * 7 * 4 + 9 * 4
    assert _added(before) == {"bank.bytes_uploaded": ebank.nbytes}


def test_counts_returns_a_copy_and_span_is_a_trace_annotation():
    spans.count("test.calls")
    spans.count("test.calls", 2)
    got = spans.counts()
    assert got["test.calls"] >= 3
    got["test.calls"] = -1
    assert spans.counts()["test.calls"] >= 3
    with spans.span("fl.test") as s:
        assert isinstance(s, jax.profiler.TraceAnnotation)
