"""The program's own marks in a profiler trace: its ``repro.*`` host spans
and the scope path of every device op.

    python3 bench/program_spans.py <trace dir or .xplane.pb>

prints, for the benchmark's window (the ``bench.window`` span), the time
in each ``repro.*`` span and the share of the training loop's wait for
input (``repro.engine.wait_chunk``) that the producer's spans cover.

``load(path)`` reads one ``.xplane.pb`` (the newest under a directory) once
and keeps, on the trace's one clock in nanoseconds:

* ``spans``: every host event named ``repro.<name>`` (``repro.tracing``
  in the program), as ``Span(name, start, end, line, stats)``, ``name``
  without its prefix, ``line`` the thread line it ran on, ``stats`` the
  span's arguments (``outcome`` of a replan join, ``steps`` of a
  dispatch);
* ``ops``: per chip, the ops of the ``XLA Ops`` line as ``(name, start,
  end, scope)``, ``scope`` being the op's scope path (the ``tf_op`` stat
  of its event metadata: the HLO ``op_name``, which holds the program's
  ``jax.named_scope`` names).  ``jax.profiler.ProfileData`` does not show
  metadata stats, so the device planes are decoded here from the
  protobuf wire format (``tsl/profiler/protobuf/xplane.proto``).

A trace of a program without these marks gives empty lists, and every
reader then returns ``None``.
"""
from __future__ import annotations

import functools
import os
import re
import sys
from typing import NamedTuple

import devtrace

PREFIX = "repro."
#: Spans that produce the loop's input (the prefetch producer's work, and
#: the replan it may wait on).
PRODUCER = ("pipeline.", "replan.", "engine.to_host", "engine.stack",
            "engine.place")


class Span(NamedTuple):
    name: str
    start: float
    end: float
    line: tuple
    stats: dict


class ProgramTrace(NamedTuple):
    spans: list        # Span, by start
    ops: dict          # chip id -> [(name, start, end, scope)], by start


# ------------------------------------------------------- protobuf wire format
def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one message: ints for varints, the
    bytes of length-delimited fields and of fixed-width ones."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire == 1:
            val, i = buf[i:i + 8], i + 8
        elif wire == 5:
            val, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, val


def _entry(buf) -> tuple[int, object]:
    """One map entry: (key, value)."""
    key = val = None
    for f, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def _device_ops(plane) -> list[tuple]:
    """The ``XLA Ops`` line of one device plane (an ``XPlane`` message):
    ``(name, start_ns, end_ns, scope)`` per op."""
    stat_names: dict[int, str] = {}
    metadata: dict[int, bytes] = {}
    ops_lines = []
    for f, v in _fields(plane):
        if f == 5:                              # stat_metadata
            key, meta = _entry(v)
            stat_names[key] = next((bytes(n).decode() for g, n
                                    in _fields(meta) if g == 2), "")
        elif f == 4:                            # event_metadata
            key, meta = _entry(v)
            metadata[key] = meta
        elif f == 3:                            # lines
            name = next((bytes(n).decode() for g, n in _fields(v) if g == 2),
                        "")
            if name == devtrace.OPS_LINE:
                ops_lines.append(v)
    tf_op = next((k for k, n in stat_names.items() if n == "tf_op"), None)

    def describe(meta) -> tuple[str, str]:
        name, scope = "", ""
        for g, v in _fields(meta):
            if g == 2:
                name = bytes(v).decode()
            elif g == 5:                        # stats
                stat = dict(_fields(v))
                if stat.get(1) == tf_op:
                    if 5 in stat:               # str_value
                        scope = bytes(stat[5]).decode()
                    elif 7 in stat:             # ref_value: a stat name
                        scope = stat_names.get(stat[7], "")
        return name, scope

    names = {k: describe(m) for k, m in metadata.items()}
    out = []
    for line in ops_lines:
        t0, events = 0, []
        for g, v in _fields(line):
            if g == 3:
                t0 = v
            elif g == 4:
                events.append(v)
        for ev in events:
            e = dict(_fields(ev))
            name, scope = names.get(e.get(1, 0), ("", ""))
            start = t0 + e.get(2, 0) / 1e3
            out.append((name, start, start + e.get(3, 0) / 1e3, scope))
    out.sort(key=lambda op: op[1])
    return out


def device_ops(path: str) -> dict[int, list[tuple]]:
    """Per chip, its ops with their scope paths (see ``_device_ops``)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for f, plane in _fields(space):
        if f != 1:
            continue
        name = next((bytes(n).decode() for g, n in _fields(plane) if g == 2),
                    "")
        if name.startswith(devtrace.DEVICE_PREFIX):
            out[int(name[len(devtrace.DEVICE_PREFIX):])] = _device_ops(plane)
    return out


# -------------------------------------------------------------------- load
@functools.lru_cache(maxsize=2)
def load(path: str) -> ProgramTrace:
    """The program's spans and the device ops of one trace (read once)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = devtrace.find_xplane(path)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(PREFIX):
                    start = float(e.start_ns)
                    spans.append(Span(e.name[len(PREFIX):], start,
                                      start + float(e.duration_ns),
                                      (plane.name, i), dict(e.stats)))
    spans.sort(key=lambda sp: sp.start)
    return ProgramTrace(spans=spans, ops=device_ops(path))


def of(rec) -> ProgramTrace | None:
    """The program trace of a traced run's record, or None."""
    if rec.trace is None or rec.probe.trace_dir is None:
        return None
    return load(rec.probe.trace_dir)


# ----------------------------------------------------------------- readers
def in_window(spans, names, lo: float, hi: float) -> list[Span]:
    """The spans named in ``names`` that start in ``[lo, hi)``."""
    return [sp for sp in spans if sp.name in names and lo <= sp.start < hi]


def per_step_ms(rec, *names: str) -> float | None:
    """Milliseconds per window step in the spans ``names`` that start in
    the window; None without a window step or without such a span."""
    trace = of(rec)
    if trace is None or not rec.probe.window_steps:
        return None
    found = in_window(trace.spans, names, *rec.trace_window)
    if not found:
        return None
    return sum(sp.end - sp.start for sp in found) / 1e6 \
        / rec.probe.window_steps


def has_scope(path: str, scope: str) -> bool:
    """Whether ``scope`` is a component of the scope path ``path``, also
    where a transformation wraps it (``jvp(repro.dnn)``); a TPU trace
    ends the path with ``:`` and the op type."""
    return re.search(r"(^|[/(])" + re.escape(scope) + r"($|[/):])",
                     path) is not None


def scope_ns(ops, scope: str, lo: float, hi: float) -> float:
    """Nanoseconds of ``[lo, hi]`` in which an op of ``scope`` ran (ops
    that only contain others left out)."""
    return devtrace.length(devtrace.union(
        [(s, e) for n, s, e, path in ops
         if has_scope(path, scope) and not devtrace.is_container(n)],
        lo, hi))


def covered_ns(spans, cover, lo: float, hi: float) -> tuple[float, float]:
    """(ns of ``[lo, hi]`` inside ``spans``, of which ns inside ``cover``)."""
    a = devtrace.union([(sp.start, sp.end) for sp in spans], lo, hi)
    b = devtrace.union([(sp.start, sp.end) for sp in cover], lo, hi)
    both = devtrace.length(a) + devtrace.length(b) \
        - devtrace.length(devtrace.union(a + b, lo, hi))
    return devtrace.length(a), both


def is_producer(name: str) -> bool:
    return name.startswith(PRODUCER)


# --------------------------------------------------------------------- main
def main(argv=None) -> int:
    from collections import defaultdict

    path = (argv or sys.argv[1:])[0]
    trace = load(path)
    bench_spans = devtrace.load(path).spans
    win = [s for s in bench_spans if s[0] == "bench.window"]
    lo, hi = (win[0][1], win[0][2]) if win else (float("-inf"),
                                                 float("inf"))
    inside = [sp for sp in trace.spans if lo <= sp.start < hi]
    total: dict[str, float] = defaultdict(float)
    count: dict[str, int] = defaultdict(int)
    for sp in inside:
        total[sp.name] += sp.end - sp.start
        count[sp.name] += 1
    print(f"window {(hi - lo) / 1e9:.3f} s" if win else "no bench.window")
    for name, ns in sorted(total.items(), key=lambda kv: -kv[1]):
        print(f"  {ns / 1e6:12.3f} ms  x{count[name]:<5d} {name}")
    waits = [sp for sp in inside if sp.name == "engine.wait_chunk"]
    wait, covered = covered_ns(
        waits, [sp for sp in trace.spans if is_producer(sp.name)], lo, hi)
    if wait:
        print(f"wait_chunk {wait / 1e6:.3f} ms, covered by producer spans "
              f"{covered / 1e6:.3f} ms ({100 * covered / wait:.2f}%)")
        # The rest, by the innermost other span open on any thread (named
        # as ``devtrace.split_gap`` names a benchmark span).
        others = bench_spans + [
            (devtrace.SPAN_PREFIX + PREFIX + sp.name, sp.start, sp.end)
            for sp in trace.spans
            if sp.name not in ("engine.wait_chunk", "engine.dispatch")]
        cover = devtrace.union([(sp.start, sp.end) for sp in trace.spans
                                if is_producer(sp.name)], lo, hi)
        rest: dict[str, float] = defaultdict(float)
        for s, e in devtrace.union([(sp.start, sp.end) for sp in waits],
                                   lo, hi):
            for gap in devtrace.gaps(devtrace.union(cover, s, e), s, e):
                for name, ns in devtrace.split_gap(gap, others).items():
                    rest[name] += ns
        for name, ns in sorted(rest.items(), key=lambda kv: -kv[1]):
            print(f"  not covered {ns / 1e6:12.3f} ms  in {name}")
    for chip, ops in sorted(trace.ops.items()):
        for scope in ("repro.chunk", "repro.dnn", "repro.graph_reg",
                      "repro.optimizer"):
            print(f"chip {chip} {scope}: "
                  f"{scope_ns(ops, scope, lo, hi) / 1e6:.3f} ms")
    return 0


if __name__ == "__main__":
    sys.exit(main())
