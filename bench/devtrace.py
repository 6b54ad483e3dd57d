"""Reduction of a profiler trace to the benchmark's device numbers.

``load(path)`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and
keeps three things, all on the trace's one clock, in nanoseconds:

* per chip, the operations that ran on it (the ``XLA Ops`` line of each
  ``/device:TPU:<n>`` plane) and the executables they belonged to (the
  ``XLA Modules`` line);
* the benchmark's host spans: every host event whose name starts with
  ``bench.`` (``jax.profiler.TraceAnnotation`` in ``harness.py``).

The functions below reduce those lists.  They take plain ``(name, start,
end)`` tuples, so the tests can drive them with hand-made events as well
as with a recorded trace.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from collections import defaultdict

Event = tuple  # (name, start_ns, end_ns)

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
#: HLO names of the cross-chip collectives (async pairs included).
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
#: Ops that only contain others (their body ops are on the same line).
CONTAINERS = ("while", "conditional", "call")
#: Host spans that frame a phase rather than name what the host does.
FRAMING_SPANS = ("bench.window", "bench.build")


@dataclasses.dataclass
class Trace:
    ops: dict[int, list[Event]]       # chip id -> ops, sorted by start
    modules: dict[int, list[Event]]   # chip id -> executables, by start
    spans: list[Event]                # host spans named bench.*, by start


def find_xplane(trace_dir: str) -> str:
    """The newest ``*.xplane.pb`` under ``trace_dir``."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def _events(line) -> list[Event]:
    out = []
    for e in line.events:
        start = float(e.start_ns)
        out.append((e.name, start, start + float(e.duration_ns)))
    out.sort(key=lambda ev: ev[1])
    return out


def load(path: str) -> Trace:
    """Read one ``.xplane.pb`` (or the newest one under a directory)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    data = ProfileData.from_file(path)
    ops: dict[int, list[Event]] = {}
    modules: dict[int, list[Event]] = {}
    spans: list[Event] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            chip = int(plane.name[len(DEVICE_PREFIX):])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[chip] = _events(line)
                elif line.name == MODULES_LINE:
                    modules[chip] = _events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [ev for ev in _events(line)
                          if ev[0].startswith(SPAN_PREFIX)]
    spans.sort(key=lambda ev: ev[1])
    return Trace(ops=ops, modules=modules, spans=spans)


# ----------------------------------------------------------------- intervals
def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged ``(start, end)`` intervals, clipped to ``[lo, hi]``."""
    merged: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy: list[tuple[float, float]], lo: float, hi: float
         ) -> list[tuple[float, float]]:
    """The stretches of ``[lo, hi]`` that ``busy`` (merged) leaves free."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def busy(ops: list[Event], lo: float, hi: float) -> list[tuple[float, float]]:
    """The merged stretches of ``[lo, hi]`` in which some operation ran.
    Ops that only contain others (a ``while`` around a scan's body) are
    left out: their body ops are on the line, and the container's own
    span would cover the bubbles between them."""
    return union([(s, e) for n, s, e in ops if not is_container(n)], lo, hi)


def busy_ns(ops: list[Event], lo: float, hi: float) -> float:
    """Nanoseconds of ``[lo, hi]`` in which some operation ran."""
    return length(busy(ops, lo, hi))


def short(name: str) -> str:
    """An op's HLO instruction name: the trace prints the whole instruction
    (``%fusion.3 = f32[...] fusion(...)``); this keeps ``%fusion.3``."""
    return name.split(" = ", 1)[0]


def opcode_base(name: str) -> str:
    """``%multiply_reduce_fusion.24`` -> ``multiply_reduce_fusion``."""
    return short(name).lstrip("%").rsplit(".", 1)[0]


def is_container(name: str) -> bool:
    return opcode_base(name) in CONTAINERS


def is_collective(name: str) -> bool:
    return any(c in short(name) for c in COLLECTIVES)


def exposed_collective_ns(ops: list[Event], lo: float, hi: float) -> float:
    """Nanoseconds in which a collective ran on the chip and no other
    operation did."""
    coll = union([(s, e) for n, s, e in ops if is_collective(n)], lo, hi)
    comp = union([(s, e) for n, s, e in ops
                  if not is_collective(n) and not is_container(n)], lo, hi)
    overlap = 0.0
    i = 0
    for s, e in coll:
        while i < len(comp) and comp[i][1] <= s:
            i += 1
        j = i
        while j < len(comp) and comp[j][0] < e:
            overlap += min(e, comp[j][1]) - max(s, comp[j][0])
            j += 1
    return length(coll) - overlap


def time_by_name(ops: list[Event], lo: float, hi: float,
                 match=None) -> dict[str, float]:
    """Summed nanoseconds per op name inside ``[lo, hi]``, for the names
    ``match(name)`` accepts (all when ``match`` is None)."""
    out: dict[str, float] = defaultdict(float)
    for n, s, e in ops:
        if (match is None or match(n)) and e > lo and s < hi:
            out[n] += min(e, hi) - max(s, lo)
    return dict(out)


def split_gap(gap: tuple[float, float], spans: list[Event]
              ) -> dict[str, float]:
    """Nanoseconds of an idle gap by what the host was doing: at every
    instant the innermost open span (the one that started last, or of two
    that started together the one that ends first) counts,
    named without its ``bench.`` prefix; ``untracked`` where none is
    open."""
    lo, hi = gap
    inside = [sp for sp in spans
              if sp[0] not in FRAMING_SPANS and sp[1] < hi and sp[2] > lo]
    cuts = sorted({lo, hi} | {t for _, s, e in inside for t in (s, e)
                              if lo < t < hi})
    out: dict[str, float] = defaultdict(float)
    for a, b in zip(cuts[:-1], cuts[1:]):
        open_ = [sp for sp in inside if sp[1] <= a and sp[2] >= b]
        inner = max(open_, key=lambda sp: (sp[1], -sp[2]), default=None)
        name = inner[0][len(SPAN_PREFIX):] if inner else "untracked"
        out[name] += b - a
    return dict(out)

