"""Runtime sanitizers paired with the flcheck static pass.

Two guards, both grounded in bug classes the static rules cannot fully
close over:

* :func:`compile_count` — a context manager that counts XLA backend
  compiles via JAX's monitoring events.  FLC001 catches the *syntactic*
  recompile patterns (``jax.jit(bound_method)`` at call time); this guard
  catches the semantic ones: tier-1 tests wrap driver runs in it and
  assert the compile count is *constant* as round counts and seed counts
  scale (a per-round or per-seed retrace shows up as a linear count).

* :func:`nan_guard` — opt-in NaN sanitizer for the FL drivers.  Flips
  ``jax_debug_nans`` for the dynamic extent of the block (and restores the
  previous value on exit), so a NaN produced inside jitted FL math raises
  ``FloatingPointError`` at the offending primitive instead of silently
  poisoning accuracy curves.  Wired to ``--sanitize-nans`` in
  ``examples/fl_noma_mnist.py``.

Implementation note: ``jax.monitoring`` listeners are process-global and
cannot be unregistered individually (only wholesale via
``clear_event_listeners``, which would drop listeners we don't own), so a
single module-level listener is installed on first use and never removed;
the context manager reads deltas of its counter.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading

import jax

# every XLA backend_compile lands exactly one of these duration events
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class _CompileCounter:
    """Process-global tally of backend-compile monitoring events."""

    def __init__(self):
        self.lock = threading.Lock()
        self.total = 0
        self.installed = False

    def _listen(self, event: str, duration: float, **kwargs) -> None:
        if event == COMPILE_EVENT:
            with self.lock:
                self.total += 1

    def install(self) -> None:
        with self.lock:
            if self.installed:
                return
            self.installed = True
        jax.monitoring.register_event_duration_secs_listener(self._listen)

    def snapshot(self) -> int:
        with self.lock:
            return self.total


_COUNTER = _CompileCounter()


@dataclasses.dataclass
class CompileTally:
    """Result handle yielded by :func:`compile_count`.

    ``count`` is None inside the block and the number of XLA backend
    compiles that occurred within it after the block exits.
    """
    count: "int | None" = None


@contextlib.contextmanager
def compile_count():
    """Count XLA backend compiles inside the block.

    >>> with compile_count() as tally:
    ...     run_horizon_scanned(...)
    >>> assert tally.count == expected

    Counts are process-wide, not thread-scoped: compiles triggered by
    other threads during the block are attributed to it.  Tests that
    assert exact counts should warm up incidental constants (e.g. a run
    at a *different* static shape) first, so the counted blocks compile
    the same set of fresh programs.
    """
    _COUNTER.install()
    tally = CompileTally()
    start = _COUNTER.snapshot()
    try:
        yield tally
    finally:
        tally.count = _COUNTER.snapshot() - start


@contextlib.contextmanager
def nan_guard(enable: bool = True):
    """Opt-in NaN sanitizer: ``jax_debug_nans`` for this dynamic extent.

    Under the guard, a NaN output from any jitted primitive re-runs
    un-jitted and raises ``FloatingPointError`` at the source.  This
    de-optimizes (per-primitive checks + possible retraces), so it is a
    debugging mode, never a default.  The previous setting is restored
    even if the block raises.

    Entering the guard clears JAX's compilation caches: on JAX 0.9.0 a
    program already compiled and dispatched through the fast path keeps
    running unchecked after the flag flips, so a NaN from it would pass.
    """
    prev = jax.config.jax_debug_nans
    jax.config.update("jax_debug_nans", bool(enable))
    if enable:
        jax.clear_caches()
    try:
        yield
    finally:
        jax.config.update("jax_debug_nans", prev)
