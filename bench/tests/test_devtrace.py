"""The trace reduction: busy/idle union, kernel time by name, exposed
collective time and gap attribution, on hand-made events and on a small
trace recorded on one TPU v5e chip (``data/record_small_trace.py``)."""
import os

import pytest

import devtrace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SMALL = os.path.join(DATA, "small.xplane.pb")


def test_union_merges_overlaps_and_clips():
    got = devtrace.union([(5, 8), (0, 2), (1, 3), (7, 12)], 1, 10)
    assert got == [(1, 3), (5, 10)]
    assert devtrace.length(got) == 7


def test_busy_and_gaps():
    ops = [("a", 0, 10), ("b", 5, 20), ("c", 30, 40)]
    assert devtrace.busy_ns(ops, 0, 50) == 30
    busy = devtrace.union([(s, e) for _, s, e in ops], 0, 50)
    assert devtrace.gaps(busy, 0, 50) == [(20, 30), (40, 50)]


def test_time_by_name_clips_to_window():
    ops = [("k1", 0, 10), ("k2", 10, 30), ("k1", 40, 60)]
    got = devtrace.time_by_name(ops, 5, 50, lambda n: n == "k1")
    assert got == {"k1": 5 + 10}


def test_exposed_collective_counts_only_uncovered_time():
    ops = [("fusion.1", 0, 10), ("all-reduce.3", 8, 20),
           ("fusion.2", 15, 17), ("all-reduce-done.4", 30, 35)]
    # all-reduce 8..20 minus compute 8..10 and 15..17 = 8; plus 5.
    assert devtrace.exposed_collective_ns(ops, 0, 100) == 13


def test_containers_count_neither_as_busy_nor_as_cover():
    ops = [("%while.6 = (s32[]) while(%t)", 0, 100),
           ("%fusion.1 = f32[] fusion(x)", 0, 10),
           ("%all-reduce.3 = f32[] all-reduce(x)", 20, 30),
           ("%fusion.2 = f32[] fusion(y)", 40, 50)]
    assert devtrace.busy_ns(ops, 0, 100) == 30
    assert devtrace.exposed_collective_ns(ops, 0, 100) == 10


def test_exposed_collective_zero_without_collectives():
    assert devtrace.exposed_collective_ns([("fusion", 0, 5)], 0, 10) == 0


def test_split_gap_counts_untracked_time():
    spans = [("bench.assemble", 0, 12), ("bench.eval", 11, 30),
             ("bench.place", 40, 41), ("bench.window", 0, 100)]
    assert devtrace.split_gap((10, 20), spans) == {"eval": 9, "assemble": 1}
    assert devtrace.split_gap((35, 39), spans) == {"untracked": 4}


def test_split_gap_prefers_the_innermost_span():
    # A chunk span that encloses two assemble spans: its own time outside
    # them (stacking) is 6 of the gap's 10, the assemble spans' 4.
    spans = [("bench.host_chunk", 0, 10), ("bench.assemble", 0, 2),
             ("bench.assemble", 3, 5)]
    assert devtrace.split_gap((0, 10), spans) == {"assemble": 4,
                                                  "host_chunk": 6}
    spans.append(("bench.assemble", 5, 9))
    assert devtrace.split_gap((0, 10), spans) == {"assemble": 8,
                                                  "host_chunk": 2}


def test_op_names():
    full = "%fusion.398 = (f32[2000]{0}, bf16[5312,2000]{0,1}) fusion(x)"
    assert devtrace.short(full) == "%fusion.398"
    assert devtrace.opcode_base(full) == "fusion"
    assert devtrace.is_container("%while.6 = (s32[]) while((s32[]) %t)")
    assert not devtrace.is_container(full)
    assert devtrace.is_collective("%all-reduce.3 = f32[] all-reduce(x)")
    assert not devtrace.is_collective(
        "%fusion.1 = f32[] fusion(%all-reduce.3)")


@pytest.fixture(scope="module")
def small():
    if not os.path.exists(SMALL):
        pytest.fail(f"missing recorded trace {SMALL}")
    return devtrace.load(SMALL)


def _window(tr):
    (w,) = [s for s in tr.spans if s[0] == "bench.window"]
    return w[1], w[2]


def test_recorded_trace_has_a_chip_and_host_spans(small):
    assert list(small.ops) == [0]
    assert {"bench.window", "bench.assemble"} <= {s[0] for s in small.spans}


def test_recorded_trace_kernel_time_by_name(small):
    lo, hi = _window(small)
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "reg_roofline", os.path.join(devtrace.__file__.rsplit(os.sep, 1)[0],
                                     "metrics", "reg_roofline.py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    got = devtrace.time_by_name(small.ops[0], lo, hi, reader.is_kernel)
    assert len(got) == 1 and 0 < sum(got.values()) < 5e6


def test_recorded_trace_idle_gap_falls_in_the_host_span(small):
    lo, hi = _window(small)
    busy = devtrace.busy(small.ops[0], lo, hi)
    idle = devtrace.gaps(busy, lo, hi)
    assert devtrace.length(idle) + devtrace.length(busy) == pytest.approx(
        hi - lo)
    longest = max(idle, key=lambda g: g[1] - g[0])
    parts = devtrace.split_gap(longest, small.spans)
    assert max(parts, key=parts.get) == "assemble"
    assert sum(parts.values()) == longest[1] - longest[0] >= 15e6


def test_split_gap_by_innermost_span():
    spans = [("bench.window", 0, 100), ("bench.host_chunk", 0, 10),
             ("bench.assemble", 0, 2), ("bench.assemble", 3, 5),
             ("bench.place", 10, 11)]
    got = devtrace.split_gap((0, 12), spans)
    assert got == {"assemble": 4, "host_chunk": 6, "place": 1,
                   "untracked": 1}
