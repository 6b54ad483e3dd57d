"""The program's spans and device scopes as the per-layer readers see them:
the protobuf decoding of the device planes, the readers on hand-made
events (window clipping, per-step division, nothing in the window), and
a whole traced run of the harness on the CPU."""
import os
import types

import pytest

import devtrace
import harness
import program_spans as ps
from program_spans import ProgramTrace, Span

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SMALL = os.path.join(DATA, "small.xplane.pb")
READERS = ("feed_wait_ms", "densify_ms", "chunk_copy_ms", "place_ms",
           "replan_wait_ms", "dnn_device_ms")


def _rec(trace_dir="t", steps=4, window=(100.0, 200.0)):
    family = harness.load_module("families", "dnn_ssl")
    return types.SimpleNamespace(
        cell=types.SimpleNamespace(family=family),
        trace=object(), trace_window=window,
        probe=types.SimpleNamespace(trace_dir=trace_dir,
                                    window_steps=steps))


def _span(name, start, end, **stats):
    return Span(name, float(start), float(end), ("/host:CPU", 0), stats)


@pytest.fixture
def fake_trace(monkeypatch):
    """``program_spans.load`` returning hand-made spans and ops."""
    def use(spans=(), ops=None):
        trace = ProgramTrace(spans=sorted(spans, key=lambda s: s.start),
                             ops=ops or {})
        monkeypatch.setattr(ps, "load", lambda path: trace)
    return use


def _read(name, rec):
    return harness.load_module("metrics", name).read(rec)


def test_decoder_matches_profile_data_and_reads_scope_paths():
    ops = ps.device_ops(SMALL)
    seen = devtrace.load(SMALL).ops
    assert sorted(ops) == sorted(seen) == [0]
    assert [op[0] for op in ops[0]] == [op[0] for op in seen[0]]
    for (_, s, e, _), (_, s2, e2) in zip(ops[0], seen[0]):
        assert abs(s - s2) <= 2 and abs(e - e2) <= 2
    scopes = {devtrace.short(n): path for n, _, _, path in ops[0]}
    assert scopes["%_fused_reg_forward.1"] == \
        "jit(_fused_reg_forward)/pallas_call:"
    assert scopes["%fusion"] == "jit(<lambda>)/dot_general:"


def test_a_trace_without_program_marks_reads_none():
    trace = ps.load(SMALL)
    assert trace.spans == []
    rec = _rec(trace_dir=SMALL, steps=3, window=(0.0, 1e12))
    for name in READERS:
        assert _read(name, rec) is None, name


@pytest.mark.parametrize("path,found", [
    ("jit(_run_chunk)/repro.chunk/while/body/repro.dnn/dot_general:", True),
    ("jit(_run_chunk)/while/body/transpose(jvp(repro.dnn))/dot_general:",
     True),
    ("jit(f)/jvp(repro.dnn)/vmap(jit(g)):fusion", True),
    ("jit(_run_chunk)/while/body/repro.dnnx/dot_general:", False),
    ("jit(_run_chunk)/while/body/repro.graph_reg/pallas_call:", False),
    ("", False),
])
def test_has_scope(path, found):
    assert ps.has_scope(path, "repro.dnn") is found


def test_scope_ns_unions_clips_and_skips_containers():
    ops = [("%while.1 = while(x)", 0, 100, "jit(f)/repro.dnn/while:"),
           ("%fusion.1 = f()", 10, 30, "jit(f)/repro.dnn/dot_general:"),
           ("%fusion.2 = f()", 20, 40, "jit(f)/jvp(repro.dnn)/tanh:"),
           ("%fusion.3 = f()", 40, 60, "jit(f)/repro.graph_reg/exp:")]
    assert ps.scope_ns(ops, "repro.dnn", 0, 100) == 30
    assert ps.scope_ns(ops, "repro.dnn", 25, 100) == 15


def test_per_step_readers_clip_to_the_window_and_divide(fake_trace):
    fake_trace([
        _span("engine.wait_chunk", 90, 150),     # starts before: left out
        _span("engine.wait_chunk", 100, 120),
        _span("engine.wait_chunk", 190, 260),    # ends after: counted whole
        _span("engine.wait_chunk", 200, 210),    # starts at the end: out
        _span("pipeline.densify", 110, 130), _span("pipeline.densify",
                                                    150, 154),
        _span("engine.to_host", 120, 128), _span("engine.stack", 160, 172),
        _span("engine.place", 180, 181)])
    rec = _rec(steps=4)
    assert _read("feed_wait_ms", rec) == pytest.approx((20 + 70) / 4 / 1e6)
    assert _read("densify_ms", rec) == pytest.approx(24 / 4 / 1e6)
    assert _read("chunk_copy_ms", rec) == pytest.approx(20 / 4 / 1e6)
    assert _read("place_ms", rec) == pytest.approx(1 / 4 / 1e6)
    assert _read("replan_wait_ms", rec) is None
    assert _read("dnn_device_ms", rec) is None


def test_replan_wait_is_per_join(fake_trace):
    fake_trace([_span("replan.join", 50, 99, outcome="swapped"),
                _span("replan.join", 120, 130, outcome="swapped"),
                _span("replan.join", 150, 180, outcome="kept")])
    assert _read("replan_wait_ms", _rec()) == pytest.approx(20 / 1e6)


def test_readers_read_none_without_a_window_step_or_a_trace(fake_trace):
    fake_trace([_span(name, 110, 120) for name in (
        "engine.wait_chunk", "pipeline.densify", "engine.to_host",
        "engine.place", "replan.join")],
               ops={0: [("%fusion.1", 110, 120, "jit(f)/repro.dnn/x:")]})
    for name in READERS:
        assert _read(name, _rec()) is not None, name
        if name != "replan_wait_ms":
            assert _read(name, _rec(steps=0)) is None, name
        untraced = _rec()
        untraced.trace = None
        assert _read(name, untraced) is None, name


def test_dnn_device_ms_is_per_step_and_averaged_over_chips(fake_trace):
    dnn = "jit(_run_chunk)/while/body/repro.dnn/dot_general:"
    fake_trace(ops={
        0: [("%fusion.1", 90, 110, dnn), ("%fusion.2", 120, 140, dnn),
            ("%fusion.3", 150, 190, "jit(_run_chunk)/repro.optimizer/x:")],
        1: [("%fusion.1", 100, 140, dnn)]})
    # Chip 0: 10 + 20 ns in the window, chip 1: 40; mean 35 over 5 steps.
    assert _read("dnn_device_ms", _rec(steps=5)) == pytest.approx(
        35 / 5 / 1e6)


def test_covered_ns_is_the_intersection():
    waits = [_span("engine.wait_chunk", 0, 10), _span("engine.wait_chunk",
                                                       20, 30)]
    cover = [_span("pipeline.block", 5, 25), _span("engine.place", 8, 9)]
    assert ps.covered_ns(waits, cover, 0, 100) == (20, 10)


def test_traced_harness_run_reports_the_program_spans(tiny_cell, tmp_path,
                                                      capsys):
    """The harness's own window over the program on the CPU, traced: the
    span readers find the program's spans; no TPU plane, so no
    ``dnn_device_ms``."""
    cell = tiny_cell()
    harness.setup_jax()
    probe = harness.Probe(seconds=0.5, prefetch=0,
                          trace_dir=str(tmp_path / "trace"))
    harness.drive(cell, 5, probe)
    rec = harness.RunRecord(cell=cell, chips=1, probe=probe, build_s=0.0,
                            window_s=probe.t_close - probe.t_open,
                            peaks=None, pad_rows=384)
    rec.trace = devtrace.load(probe.trace_dir)
    win, = [s for s in rec.trace.spans if s[0] == "bench.window"]
    rec.trace_window = (win[1], win[2])
    got = {name: _read(name, rec) for name in READERS}
    for name in ("feed_wait_ms", "densify_ms", "chunk_copy_ms", "place_ms"):
        assert got[name] > 0, name
    assert got["dnn_device_ms"] is None
    window_ms = (win[2] - win[1]) / 1e6
    for name in ("feed_wait_ms", "densify_ms", "chunk_copy_ms"):
        assert got[name] * probe.window_steps <= window_ms, name
    assert ps.main([probe.trace_dir]) == 0
    out = capsys.readouterr().out
    assert "engine.wait_chunk" in out and "covered by producer" in out
