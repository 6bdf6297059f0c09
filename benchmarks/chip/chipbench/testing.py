"""Small cells for the CPU tests: a cell of ``BENCHMARK.json`` with its
configuration cut to 24 devices, eight rounds and 1,200 samples, and the
program faults the tests plant under a run.  A fault is made for a cell
(``FAULTS[name](cell)``) and planted with ``with``."""
from __future__ import annotations

import contextlib
import copy

import numpy as np

from chipbench import harness

TINY = {"num_devices": 24, "num_rounds": 8}
TINY_SAMPLES = 1200


def tiny_cell(name):
    cell = harness.load_cell(name)
    cell.config = copy.deepcopy(cell.config)
    cell.config["fl"].update(TINY)
    cell.config["data"]["num_samples"] = TINY_SAMPLES
    return cell


@contextlib.contextmanager
def patched(obj, attr, value):
    old = getattr(obj, attr)
    setattr(obj, attr, value)
    try:
        yield
    finally:
        setattr(obj, attr, old)


def _unchanged_state(cell):
    """The round body returns the parameters it was given."""
    from repro.core import fl_engine

    real = fl_engine._train_quantize_aggregate

    def fault(params, *args, **kwargs):
        _, bits, kept, norms = real(params, *args, **kwargs)
        return params, bits, kept, norms

    return patched(fl_engine, "_train_quantize_aggregate", fault)


def _half_batch(cell):
    """Each SGD step of the cell's model sees the first half of its
    minibatch, and the loss is the mean over that half."""
    from repro.models.fl_models import get_fl_model

    model = type(get_fl_model(cell.config["fl"]["model"]))
    real = model.batch_loss

    def fault(self, params, bx, by, valid):
        h = bx.shape[0] // 2
        return real(self, params, bx[:h], by[:h], valid[:h])

    return patched(model, "batch_loss", fault)


def _altered_schedule(cell):
    """The scheduler's first round names another device than it chose:
    the first devices of rounds 0 and 1 trade places (precomputed plans),
    or round 0 lists a device the scan did not choose (online)."""
    from repro.core import fl_engine, scheduling

    @contextlib.contextmanager
    def both():
        real = scheduling._greedy_rounds_jax_fused
        real_online = fl_engine.run_horizon_online

        def fault(*args, **kwargs):
            rounds = list(real(*args, **kwargs))
            a, b = rounds[0], rounds[1]
            rounds[0] = (b[0],) + tuple(a[1:])
            rounds[1] = (a[0],) + tuple(b[1:])
            return rounds

        def fault_online(params, solo_tm, *args, **kwargs):
            out = list(real_online(params, solo_tm, *args, **kwargs))
            first = set(np.asarray(out[1][0]).tolist())
            spare = min(set(range(solo_tm.shape[1])) - first)
            out[1] = out[1].at[0, 0].set(spare)
            return tuple(out)

        with patched(scheduling, "_greedy_rounds_jax_fused", fault), \
                patched(fl_engine, "run_horizon_online", fault_online):
            yield

    return both()


FAULTS = {
    "unchanged_state": _unchanged_state,
    "half_batch": _half_batch,
    "altered_schedule": _altered_schedule,
}


def run_tiny(name, seed, fault=None):
    """One CPU run of the tiny ``name`` cell, with ``fault`` planted."""
    import jax

    cell = tiny_cell(name)
    jax.clear_caches()
    ctx = FAULTS[fault](cell) if fault else contextlib.nullcontext()
    with ctx:
        return harness.run(cell, seed=seed, seconds=0.0, trace=False,
                           require_tpu=False)
