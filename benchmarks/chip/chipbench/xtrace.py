"""Reduction of a JAX profiler trace to the events the metrics read.

``load`` reads the ``.xplane.pb`` the profiler writes with nothing but
JAX (``jax.profiler.ProfileData``) and keeps three kinds of event:

  ops      device operations: the "XLA Ops" line of every "/device:TPU:n"
           plane, each with the XLA module it ran in
  modules  XLA program executions: the "XLA Modules" line of those planes
  spans    the benchmark's own host spans (``TraceAnnotation`` names that
           start with ``bench.``)

Every time is in nanoseconds on the profiler's one clock.  A reduced
trace round-trips through JSON (``to_json`` / ``from_json``), which is how
the tests keep a small recorded chip trace beside them.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"/device:TPU:(\d+)$")
SPAN_PREFIX = "bench."
CALL_SPAN = "bench.call"


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float
    chip: int = -1         # device ordinal; -1 for host spans
    module: str = ""       # XLA module of a device op

    @property
    def end_ns(self):
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    ops: list
    modules: list
    spans: list
    chips: int

    def to_json(self):
        return {"chips": self.chips,
                **{k: [dataclasses.astuple(e) for e in getattr(self, k)]
                   for k in ("ops", "modules", "spans")}}

    @classmethod
    def from_json(cls, d):
        return cls(*[[Event(*e) for e in d[k]]
                     for k in ("ops", "modules", "spans")], d["chips"])


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def op_name(name):
    """The HLO instruction's name, without the text of the instruction
    that the TPU's op events carry after it."""
    return name.split(" = ", 1)[0].lstrip("%")[:120]


def _module_of(event):
    for key, value in event.stats:
        if key == "hlo_module":
            return str(value)
    return ""


def load(path):
    from jax.profiler import ProfileData

    ops, modules, spans, chips = [], [], [], set()
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in (OPS_LINE, MODULES_LINE):
                chip = int(m.group(1))
                chips.add(chip)
                for e in line.events:
                    if line.name == OPS_LINE:
                        ops.append(Event(op_name(e.name), e.start_ns,
                                         e.duration_ns, chip, _module_of(e)))
                    else:
                        modules.append(Event(e.name, e.start_ns,
                                             e.duration_ns, chip))
            elif not m:
                spans.extend(Event(e.name, e.start_ns, e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return Trace(ops, modules, spans, len(chips))


def window(trace):
    """(start, end) of the measured window: the first to the last call."""
    calls = [s for s in trace.spans if s.name == CALL_SPAN]
    if not calls:
        return None
    return min(s.start_ns for s in calls), max(s.end_ns for s in calls)


def _merged(intervals, lo, hi):
    out = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def busy_ns(trace, lo, hi):
    """Per chip, the length of the union of its op intervals in [lo, hi]."""
    return [sum(b - a for a, b in _merged(
        [(e.start_ns, e.end_ns) for e in trace.ops if e.chip == c], lo, hi))
        for c in sorted({e.chip for e in trace.ops})]


def idle_gaps(trace, lo, hi, top=10):
    """The longest stretches of [lo, hi] in which no chip ran an op, each
    named by the host span that covers its middle."""
    busy = _merged([(e.start_ns, e.end_ns) for e in trace.ops], lo, hi)
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, b in gaps[:top]:
        mid = 0.5 * (a + b)
        cover = [s for s in trace.spans if s.start_ns <= mid <= s.end_ns]
        name = min(cover, key=lambda s: s.dur_ns).name if cover else "none"
        out.append([name, (b - a) * 1e-9])
    return out


def top_ops(trace, lo, hi, top=10):
    """The device ops (by module and name) that took the most time."""
    total = {}
    for e in trace.ops:
        a, b = max(e.start_ns, lo), min(e.end_ns, hi)
        if b > a:
            key = f"{e.module}/{e.name}" if e.module else e.name
            total[key] = total.get(key, 0.0) + (b - a)
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:top]
    return [[k, v * 1e-9] for k, v in ranked]


def matches(module, names):
    """True when an XLA module is the program of one of the jitted
    functions ``names`` (module ``jit_<name>``, with any suffix)."""
    base = re.sub(r"^jit_", "", module)
    return any(re.fullmatch(re.escape(n) + r"([.(\[].*)?", base)
               for n in names)


def module_ns(trace, names, lo, hi):
    """Device time, summed over chips, of the executions of ``names``."""
    total = 0.0
    for e in trace.modules:
        if matches(e.name, names):
            total += max(0.0, min(e.end_ns, hi) - max(e.start_ns, lo))
    return total
