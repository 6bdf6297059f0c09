"""Host spans and counters of the FL drivers.

``span(name)`` marks a stretch of host work as a
``jax.profiler.TraceAnnotation``: when a profiler trace is running, the
span is recorded on the profiler's clock beside the device ops; otherwise
it costs about a microsecond.  ``count(name, n)`` adds to a process-wide
tally of plain ints, and ``counts()`` returns a copy of it.

Both belong to host code only.  Inside jit-traced code a span would time
the tracing, not the work, and a count would run once per compile
(``python -m tools.flcheck`` flags either there: FLC008).
"""
from __future__ import annotations

import jax

_COUNTS: dict = {}


def span(name: str):
    """A context manager recording ``name`` on the profiler's timeline."""
    return jax.profiler.TraceAnnotation(name)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the process-wide counter ``name``."""
    _COUNTS[name] = _COUNTS.get(name, 0) + int(n)


def counts() -> dict:
    """A copy of every counter: ``{name: int}``."""
    return dict(_COUNTS)
