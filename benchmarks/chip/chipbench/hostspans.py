"""The program's own host spans in a profiler trace.

The FL drivers mark their host layers with ``repro.utils.spans`` (a
``TraceAnnotation`` each, named ``fl.*``; the README's "Tracing a
horizon" lists them).  ``xtrace.load`` keeps only the benchmark's
``bench.*`` spans; ``load`` here keeps the program's beside them, so an
idle gap is named by the innermost program span over it, and each host
layer's time can be read:

  span_self_ns    every span of one name, clipped to the window, minus
                  the union of the spans nested inside it
  host_ms         that per instance planned in the window, in ms
  untraced_share  the part of the calls' time that no program span but
                  the root ``fl.horizon`` covers

The self times of ``PARTS`` partition a call's time under ``fl.horizon``:
the spans nest (``fl.power`` in ``fl.schedule`` in ``fl.plan``, the
scheduler's ``fl.sync`` in ``fl.schedule``), so no time counts twice.
"""
from __future__ import annotations

import bisect

from chipbench import xtrace

FL_PREFIX = "fl."
ROOT_SPAN = "fl.horizon"
PARTS = ("fl.plan", "fl.schedule", "fl.power", "fl.bank", "fl.dispatch",
         "fl.sync", "fl.replay")


def load(path):
    """``xtrace.load``, with the program's ``fl.*`` host spans kept too."""
    from jax.profiler import ProfileData

    tr = xtrace.load(path)
    for plane in ProfileData.from_file(path).planes:
        if xtrace.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            tr.spans.extend(xtrace.Event(e.name, e.start_ns, e.duration_ns)
                            for e in line.events
                            if e.name.startswith(FL_PREFIX))
    return tr


def span_self_ns(trace, name, lo, hi):
    """Time of the spans named ``name`` in [lo, hi], less the spans nested
    inside them."""
    spans = sorted(trace.spans, key=lambda e: e.start_ns)
    starts = [e.start_ns for e in spans]
    total = 0.0
    for s in spans:
        a, b = max(s.start_ns, lo), min(s.end_ns, hi)
        if s.name != name or b <= a:
            continue
        inner = [(e.start_ns, e.end_ns) for e in spans[
            bisect.bisect_left(starts, s.start_ns):
            bisect.bisect_right(starts, s.end_ns)]
            if e is not s and e.end_ns <= s.end_ns]
        total += (b - a) - sum(y - x for x, y in xtrace._merged(inner, a, b))
    return total


def host_ms(trace, name, lo, hi, instances):
    """Self time of ``name`` per instance, ms; None where the window holds
    no such span."""
    ns = span_self_ns(trace, name, lo, hi)
    if ns <= 0 or instances <= 0:
        return None
    return ns * 1e-6 / instances


def untraced_share(trace, lo, hi):
    """Share of the calls' time in [lo, hi] that no ``fl.*`` span other
    than ``fl.horizon`` covers; None without calls."""
    calls = xtrace._merged([(s.start_ns, s.end_ns) for s in trace.spans
                            if s.name == xtrace.CALL_SPAN], lo, hi)
    total = sum(b - a for a, b in calls)
    if total <= 0:
        return None
    covered = xtrace._merged([(s.start_ns, s.end_ns) for s in trace.spans
                              if s.name.startswith(FL_PREFIX)
                              and s.name != ROOT_SPAN], lo, hi)
    inside = sum(max(0.0, min(b, d) - max(a, c))
                 for a, b in calls for c, d in covered)
    return 1.0 - inside / total


def iters_per_group(counters):
    """MAPEL polyblock iterations per group solved, from the program's
    ``power.*`` counters; None where no group was solved."""
    groups = counters.get("power.mapel_groups", 0)
    if groups <= 0:
        return None
    return counters.get("power.mapel_iters", 0) / groups
