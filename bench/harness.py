"""One run of one benchmark cell: build, warm up, measure, check.

The cell's configuration and traffic files (named in ``BENCHMARK.json``)
become one ``ExperimentConfig`` through the configuration's family module
(``bench/families/<reference>.py``, ``cell.family``); the run then drives
the program's normal path, ``Experiment.run`` -> ``Engine.run`` -> the
family's loss -> the PAIRWISE kernels, with the prefetching
``MetaBatchStream`` producer on.  This module knows only the graph-SSL
contract that every family shares (``bench/families/__init__.py``): what
``Experiment`` and ``Engine`` provide, every batch's ``valid`` of shape
(k, P), the chunk metrics ``loss/total`` and ``loss/graph``, and the keys
``pad_rows``, ``prefetch`` and ``repartition_every_n_epochs`` of the
traffic.  Whatever knows the model (its ``ExperimentConfig``, the held-out
eval, its state on the host, the checks against its reference, its FLOPs)
is the family's.

The benchmark watches that path from outside, through wrappers it puts
around its calls for the length of the run (``instrument``):

* ``Engine._chunk_fn`` (the jitted scan over one chunk of steps) gives the
  chunk boundaries.  The first chunk compiles (or loads from the
  persistent cache); its input state, its per-step losses and its output
  state are copied to the host for the correctness check.  The window
  opens at a device sync at the end of the first later chunk at whose
  start no other placed chunk waited in the prefetch queue (at most
  ``2 + prefetch`` chunks in), so that it starts from the steady state and
  not from a queue filled during the compile.  It closes at the first
  chunk boundary at or after ``--seconds``, again at a device sync, and
  ends the run.
* The pipeline's epoch iterator (batch assembly, one ``next()`` per step),
  the engine's grouping of steps into chunks, the strategy's
  ``place_batch`` (host -> device) and the held-out eval get host spans
  (``assemble``, ``host_chunk``, ``place``, ``eval``; the eval call is
  the one the family names in ``EVAL``) and counts.  Spans
  also go to the profiler trace through ``jax.profiler.TraceAnnotation``,
  named ``bench.<span>``.

After the window, with the program's threads joined, the check that
decides ``correct`` (the family's ``reference_numbers``, ``kernel_check``
and ``batch_check``, by ``bench/correctness.py``) compares the first chunk
with the plain reference (``bench/references/<reference>.py``): the steps
(losses, regularizer values, gradient and update norms), the regularizer
kernels at the timed shapes, the batches against the corpus and the
reference's own k-NN graph, and the replans the run owed against those
swapped in.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import os
import sys
import tempfile
import threading
import time

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

#: Threads the program starts for a run; all are joined before the check.
PROGRAM_THREADS = ("engine-prefetch", "metabatch-repartition")
THREAD_JOIN_S = 300.0


class WindowClosed(Exception):
    """Raised from the chunk wrapper once the window has closed."""


# --------------------------------------------------------------- the spec
def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with everything its files say."""

    name: str
    chips: int
    config: dict        # bench/configs/<config>.json
    traffic: dict       # bench/traffic/<traffic>.json
    limits: dict        # bench/limits/<cell>.json
    end_to_end: list    # BENCHMARK.json metric entries this cell reports
    per_layer: list
    family: object      # bench/families/<reference>.py


def reports(metric: dict, cell: str, end_to_end: list) -> bool:
    """Whether ``cell`` reports ``metric`` (an end-to-end or per-layer
    entry): listed cells where given, else every cell that reports the
    end-to-end metric it moves, else every cell."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    if "moves" in metric:
        moved = [m for m in end_to_end if m["name"] == metric["moves"]]
        return bool(moved) and reports(moved[0], cell, end_to_end)
    return True


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json``, every file of it read
    under ``root``: configuration, traffic, limits and family module."""
    bench_dir = os.path.join(root, os.path.basename(BENCH_DIR))
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    e2e = [m for m in spec["end_to_end"]
           if reports(m, name, spec["end_to_end"])]
    layer = [m for m in spec["per_layer"]
             if reports(m, name, spec["end_to_end"])]
    config = load_json(os.path.join(root, conf["file"]))
    family = os.path.join(bench_dir, "families", config["reference"] + ".py")
    if not os.path.exists(family):
        raise FileNotFoundError(
            f"configuration {conf['name']!r} names the family "
            f"{config['reference']!r}, but there is no {family}")
    return Cell(
        name=name, chips=int(w["chips"]), config=config,
        traffic=load_json(os.path.join(bench_dir, "traffic",
                                       w["traffic"] + ".json")),
        limits=load_json(os.path.join(bench_dir, "limits", name + ".json")),
        end_to_end=e2e, per_layer=layer,
        family=load_module("families", config["reference"], bench_dir))


def seeds_of(seed: int) -> dict:
    """Independent 31-bit seeds for the corpus, the partition stream and
    the model, drawn from the run's ``--seed`` (any non-negative int)."""
    words = np.random.SeedSequence(int(seed)).generate_state(3)
    data, partition, model = (int(w) & 0x7FFFFFFF for w in words)
    return {"data": data, "partition": partition, "model": model}


# ------------------------------------------------------------------ spans
class Spans:
    """Host spans: ``(name, start_s, end_s)`` on ``time.perf_counter``,
    written to the profiler trace as ``bench.<name>`` as well."""

    def __init__(self):
        self.items: list[tuple[str, float, float]] = []
        self._lock = threading.Lock()

    @contextlib.contextmanager
    def span(self, name: str):
        import jax
        with jax.profiler.TraceAnnotation("bench." + name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                t1 = time.perf_counter()
                with self._lock:
                    self.items.append((name, t0, t1))

    def between(self, name: str, lo: float, hi: float) -> list[float]:
        """Durations (s) of the ``name`` spans that started in [lo, hi)."""
        with self._lock:
            return [e - s for n, s, e in self.items
                    if n == name and lo <= s < hi]


# ------------------------------------------------------------------ probe
@dataclasses.dataclass
class Probe:
    """What the wrappers record during one run."""

    seconds: float
    prefetch: int
    trace_dir: str | None
    first_only: bool = False         # stop after the first chunk (calibration)
    spans: Spans = dataclasses.field(default_factory=Spans)
    phase: str = "warmup"
    produced: int = 0                # chunks placed on the device
    consumed: int = 0                # chunks handed to the compiled scan
    steps: int = 0                   # steps dispatched so far
    window_first_step: int = 0
    window_steps: int = 0
    window_chunks: int = 0
    t_open: float = 0.0
    t_close: float = 0.0
    first_chunk: dict | None = None  # host batches of the first chunk
    epochs_begun: list = dataclasses.field(default_factory=list)
    first: dict | None = None        # program state around the first chunk
    losses: list = dataclasses.field(default_factory=list)  # (step0, array)
    final_finite: bool | None = None
    memory_peak_bytes: int | None = None
    # Per step, in the order the pipeline produced them.
    step_frames: list = dataclasses.field(default_factory=list)
    step_valid: list = dataclasses.field(default_factory=list)
    compile_requests: int = 0        # backend compile or cache fetch
    cache_hits: int = 0
    window_compile_requests: int = 0
    window_cache_hits: int = 0
    _annotation: object = None
    marks: dict = dataclasses.field(default_factory=dict)  # set-up times

    # Listeners of jax.monitoring (process-wide; counted in every phase).
    def on_event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
            if self.phase == "window":
                self.window_cache_hits += 1

    def on_duration(self, event: str, _secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_requests += 1
            if self.phase == "window":
                self.window_compile_requests += 1

    @property
    def window_compiles(self) -> int:
        return self.window_compile_requests - self.window_cache_hits

    def open(self):
        import jax
        if self.trace_dir is not None:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self._annotation = jax.profiler.TraceAnnotation("bench.window")
            self._annotation.__enter__()
        self.phase = "window"
        self.window_first_step = self.steps
        self.t_open = time.perf_counter()

    def close(self):
        import jax
        self.t_close = time.perf_counter()
        self.phase = "closed"
        self.window_steps = self.steps - self.window_first_step
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
            jax.profiler.stop_trace()

    def window_valid(self) -> list:
        """Per window step, the valid rows of each worker's block."""
        return self.step_valid[self.window_first_step:
                               self.window_first_step + self.window_steps]

    def window_frames(self) -> float:
        return float(sum(self.step_frames[
            self.window_first_step:self.window_first_step
            + self.window_steps]))


class _CompileEvents:
    """Forwards JAX's compile events to the probe of the run in progress.
    JAX keeps its listeners for the life of the process, so one pair is
    registered once and later runs in the process (calibration) only swap
    the probe."""

    def __init__(self):
        self.probe: Probe | None = None
        self._registered = False

    def listen(self, probe: Probe) -> None:
        import jax
        self.probe = probe
        if not self._registered:
            jax.monitoring.register_event_listener(self._event)
            jax.monitoring.register_event_duration_secs_listener(
                self._duration)
            self._registered = True

    def _event(self, event: str, **kw):
        if self.probe is not None:
            self.probe.on_event(event, **kw)

    def _duration(self, event: str, secs: float, **kw):
        if self.probe is not None:
            self.probe.on_duration(event, secs, **kw)


_COMPILES = _CompileEvents()


def _chunk_wrapper(fn, engine, probe: Probe, host_state):
    """The engine's jitted chunk, with the window's boundaries;
    ``host_state`` is the family's."""
    import jax

    def chunk(carry, placed, lr, capture=False):
        steps = int(jax.tree.leaves(placed)[0].shape[0])
        staged = probe.produced - (probe.consumed + 1)
        if probe.consumed == 0:
            before = host_state(engine.strategy.state_of(carry))
        out = fn(carry, placed, lr, capture)
        probe.consumed += 1
        probe.losses.append((probe.steps, out[1]["loss/total"]))
        probe.steps += steps
        if probe.phase == "warmup":
            if probe.consumed == 1:
                jax.block_until_ready(out)
                probe.marks["first_chunk"] = time.perf_counter()
                after = host_state(engine.strategy.state_of(out[0]))
                probe.first = {
                    "params0": before["params"], "params": after["params"],
                    "accum": after["accum"],
                    "losses": np.asarray(jax.device_get(
                        out[1]["loss/total"]), np.float64),
                    "greg": np.asarray(jax.device_get(
                        out[1]["loss/graph"]), np.float64)}
                if probe.first_only:
                    probe.phase = "closed"
                    raise WindowClosed
            if (probe.consumed >= 2 and staged <= 0) \
                    or probe.consumed >= 2 + probe.prefetch:
                jax.block_until_ready(out)
                probe.open()
        elif probe.phase == "window":
            probe.window_chunks += 1
            if time.perf_counter() - probe.t_open >= probe.seconds:
                jax.block_until_ready(out)
                probe.close()
                params = engine.strategy.state_of(out[0]).params
                probe.final_finite = bool(all(
                    jax.device_get(jax.numpy.isfinite(p).all())
                    for p in jax.tree.leaves(params)))
                probe.memory_peak_bytes = memory_peak_bytes()
                raise WindowClosed
        return out

    return chunk


def memory_peak_bytes() -> int | None:
    """The peak bytes in use on the fullest local device."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


@contextlib.contextmanager
def instrument(exp, probe: Probe, family):
    """Put the probe's wrappers around the program's calls for one run."""
    from repro.api.registry import STRATEGY
    from repro.train.engine import Engine

    strategy_cls = STRATEGY.get(exp._strategy())
    orig_init = Engine.__init__
    orig_place = strategy_cls.place_batch
    module_name, eval_name = family.EVAL
    eval_module = importlib.import_module(module_name)
    orig_eval = getattr(eval_module, eval_name)
    orig_pipeline = exp.pipeline
    stream = orig_pipeline.stream
    k = exp.config.train.n_workers
    n = exp.corpus.n

    orig_host_chunks = Engine._host_chunks

    def init(self, *a, **kw):
        orig_init(self, *a, **kw)
        self._chunk_fn = _chunk_wrapper(self._chunk_fn, self, probe,
                                        family.host_state)

    def host_chunks(self, *a, **kw):
        # One span per chunk the producer groups: its steps' assembly (the
        # nested assemble spans) plus the batch copies and the stacking.
        it = orig_host_chunks(self, *a, **kw)
        while True:
            with probe.spans.span("host_chunk"):
                try:
                    chunk = next(it)
                except StopIteration:
                    return
            yield chunk

    def place(self, chunk):
        if probe.first_chunk is None:
            probe.first_chunk = chunk
        with probe.spans.span("place"):
            placed = orig_place(self, chunk)
        probe.produced += 1
        return placed

    def evaluate(*a, **kw):
        with probe.spans.span("eval"):
            return orig_eval(*a, **kw)

    def pipeline(epoch=None, n_epochs=None):
        it = iter(orig_pipeline(epoch=epoch, n_epochs=n_epochs))
        frames = None
        while True:
            with probe.spans.span("assemble"):
                try:
                    batch = next(it)
                except StopIteration:
                    return
            if frames is None:
                # The epoch's plan is in place once its first batch is out.
                probe.epochs_begun.append(epoch)
                # Every step of an epoch trains on n / steps frames on
                # average: an epoch's main meta-batches hold the corpus once.
                frames = n / math.ceil(stream.plan.n_meta / k)
            probe.step_frames.append(frames)
            probe.step_valid.append(np.asarray(batch.valid).sum(axis=-1))
            yield batch

    pipeline.stream = stream
    Engine.__init__ = init
    Engine._host_chunks = host_chunks
    strategy_cls.place_batch = place
    setattr(eval_module, eval_name, evaluate)
    exp.pipeline = pipeline
    try:
        yield
    finally:
        Engine.__init__ = orig_init
        Engine._host_chunks = orig_host_chunks
        strategy_cls.place_batch = orig_place
        setattr(eval_module, eval_name, orig_eval)
        exp.pipeline = orig_pipeline


def join_program_threads(timeout: float = THREAD_JOIN_S) -> list[str]:
    """Join the threads the program started; the names of any left."""
    deadline = time.monotonic() + timeout
    for t in threading.enumerate():
        if t.name in PROGRAM_THREADS:
            t.join(max(0.0, deadline - time.monotonic()))
    return [t.name for t in threading.enumerate()
            if t.name in PROGRAM_THREADS and t.is_alive()]


# -------------------------------------------------------------------- run
@dataclasses.dataclass
class RunRecord:
    """What the per-layer metric readers get (``bench/metrics/*.py``)."""

    cell: Cell
    chips: int
    probe: Probe
    build_s: float
    window_s: float
    peaks: dict | None
    trace: object = None            # devtrace.Trace of the window, or None
    trace_window: tuple = (0.0, 0.0)  # its bounds on the trace's clock (ns)
    pad_rows: int = 0


def load_module(kind: str, name: str, bench_dir: str = BENCH_DIR):
    """``<bench_dir>/<kind>/<name>.py`` (a family, a reference or a metric
    reader), found by name; names may hold dots, so it is loaded from its
    path."""
    path = os.path.join(bench_dir, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def setup_jax() -> str:
    """The program's compile cache, in the directory the benchmark gives
    it, keeping every program (also those that compile in under a second)
    so that only a cell's first run in a checkout compiles."""
    import jax

    from repro.compile_cache import enable_compilation_cache
    cache_dir = enable_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir


def pin_pad(exp, rows: int | None):
    """``exp`` with its batches padded to ``rows`` rows, whatever the
    seed's initial plan: the pipeline pads to the largest meta-batch (plus
    neighbour) times ``pad_headroom``, so the headroom is set to land on
    ``rows``.  Every seed then runs the same shapes and the same programs.
    The corpus, graph and plan already built are kept."""
    import dataclasses as dc

    from repro.api import Experiment
    if rows is None or exp.pipeline.stream.pad == rows:
        return exp
    cfg = exp.config
    mmax = max(len(m) for m in exp.plan.meta_batches)
    base = 2 * mmax if cfg.batch.with_neighbor else mmax
    # The stream rounds base * headroom up to a multiple of 64.
    headroom = (rows - 32) / base
    if headroom < 1.0 or rows % 64:
        raise ValueError(f"cannot pad to {rows} rows: the plan needs {base}")
    cfg = dc.replace(cfg, batch=dc.replace(cfg.batch, pad_headroom=headroom))
    exp = Experiment(cfg, corpus=exp.corpus, eval_data=exp.eval_data,
                     graph=exp.graph, plan=exp.plan).build()
    if exp.pipeline.stream.pad != rows:
        raise RuntimeError(f"padded to {exp.pipeline.stream.pad}, not {rows}")
    return exp


def drive(cell: Cell, seed: int, probe: Probe) -> dict:
    """Build the cell's experiment and train it under the probe until the
    probe ends the run; every program thread is joined on return."""
    from repro.api import Experiment

    cfg = cell.family.experiment_config(cell.config, cell.traffic,
                                        seeds_of(seed))
    probe.prefetch = cell.traffic["prefetch"]
    _COMPILES.listen(probe)
    exp = Experiment(cfg)
    with probe.spans.span("build"):
        exp.build()
        exp = pin_pad(exp, cell.traffic.get("pad_rows"))
    build_s = probe.spans.items[-1][2] - probe.spans.items[-1][1]
    probe.marks["built"] = time.perf_counter()
    info = {"build_s": build_s, "pad": exp.pipeline.stream.pad,
            "n_meta": exp.plan.n_meta}
    log(f"bench: built n={exp.corpus.n} n_meta={info['n_meta']} "
        f"pad={info['pad']} in {build_s:.3f} s")
    cell.family.warm_eval(exp)
    probe.marks["eval_warm"] = time.perf_counter()
    with instrument(exp, probe, cell.family):
        try:
            exp.run()
        except WindowClosed:
            pass
    if probe.phase != "closed":
        raise RuntimeError(f"the run ended in phase {probe.phase!r} before "
                           "its window closed")
    left = join_program_threads()
    if left:
        raise RuntimeError(f"program threads still alive: {left}")
    # A re-partitioning epoch whose plan was not swapped in ran on the
    # previous plan (a replan that failed or did not fit the pinned pad).
    every = cell.traffic["repartition_every_n_epochs"]
    info["replans_due"] = sum(1 for e in set(probe.epochs_begun)
                              if every > 0 and e > 0 and e % every == 0)
    info["replans_swapped"] = exp.pipeline.stream.swaps
    info["corpus"] = exp.corpus
    return info


def run_cell(cell: Cell, *, seed: int, seconds: float, traced: bool,
             t_start: float, trace_keep: str | None = None) -> dict:
    """One run; returns the result object (the last stdout line)."""
    import jax

    setup_jax()
    devices = jax.devices()[:cell.chips]
    kind = devices[0].device_kind
    trace_dir = None
    if traced:
        trace_dir = trace_keep or tempfile.mkdtemp(prefix="bench_trace_")
    probe = Probe(seconds=seconds, prefetch=0, trace_dir=trace_dir)
    info = drive(cell, seed, probe)
    build_s, pad = info["build_s"], info["pad"]
    setup_s = probe.t_open - t_start
    window_s = probe.t_close - probe.t_open
    m = probe.marks
    log(f"bench: set-up {setup_s:.3f} s: to build "
        f"{m['built'] - build_s - t_start:.3f}, build {build_s:.3f}, eval "
        f"warm-up {m['eval_warm'] - m['built']:.3f}, first chunk "
        f"{m['first_chunk'] - m['eval_warm']:.3f}, further warm-up "
        f"{probe.t_open - m['first_chunk']:.3f}")
    log(f"bench: warm-up {probe.window_first_step} steps, window "
        f"{probe.window_steps} steps in {probe.window_chunks} chunks, "
        f"{window_s:.3f} s; compile requests {probe.compile_requests} "
        f"(cache hits {probe.cache_hits})")
    if probe.window_compiles:
        log(f"bench: WARNING {probe.window_compiles} compilations inside the "
            f"window ({probe.window_cache_hits} cache loads)")
    elif probe.window_cache_hits:
        log(f"bench: {probe.window_cache_hits} programs loaded from the "
            "persistent cache inside the window, none compiled")

    losses = np.concatenate([np.asarray(jax.device_get(a), np.float64)
                             for s, a in probe.losses
                             if s >= probe.window_first_step])
    failed = int(np.sum(~np.isfinite(losses)))
    # Free the program's state before the reference runs.
    first_chunk, first = probe.first_chunk, probe.first
    probe.first_chunk = probe.first = None
    probe.losses.clear()
    gc.collect()

    result = {"correct": False, "attempted": probe.window_steps,
              "failed": failed}
    t_ref = time.perf_counter()
    numbers = cell.family.reference_numbers(cell, seed, first_chunk, first)
    numbers.update(cell.family.kernel_check(cell, seed, first_chunk))
    numbers.update(cell.family.batch_check(cell, info, first_chunk))
    info.pop("corpus")
    log(f"bench: reference check {time.perf_counter() - t_ref:.3f} s; "
        f"replans due {info['replans_due']}, swapped "
        f"{info['replans_swapped']}")
    from correctness import judge
    ok, lines = judge(numbers, cell.limits["limits"])
    ok = ok and failed == 0 and bool(probe.final_finite) \
        and probe.window_compiles == 0
    del first_chunk

    peaks = None
    try:
        from peaks import peaks_for
        peaks = peaks_for(kind)
    except KeyError as e:
        if traced:
            raise
        log(f"bench: {e}")
    rec = RunRecord(cell=cell, chips=cell.chips, probe=probe,
                    build_s=build_s, window_s=window_s, peaks=peaks,
                    pad_rows=pad)
    device = {"platform": devices[0].platform, "kind": kind,
              "count": cell.chips,
              "memory_peak_bytes": probe.memory_peak_bytes}
    if traced:
        import devtrace as tr
        rec.trace = tr.load(trace_dir)
        win = [s for s in rec.trace.spans if s[0] == "bench.window"]
        rec.trace_window = (win[0][1], win[0][2]) if win else (0.0, 0.0)
        metrics = {}
        for m in cell.per_layer:
            val = load_module("metrics", m["name"]).read(rec)
            if val is not None:
                metrics[m["name"]] = {"value": val, "unit": m["unit"]}
        busy = _busy(rec)
        device["busy_s"] = busy
        device["window_s"] = (rec.trace_window[1] - rec.trace_window[0]) / 1e9
        result["breakdown"] = breakdown(rec)
        if trace_keep is None:
            import shutil
            shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        values = {"frames_per_s": probe.window_frames() / window_s,
                  "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    result.update(correct=bool(ok), metrics=metrics, device=device)
    checks = {k: {"value": numbers[k], "limit": v}
              for k, v in cell.limits["limits"].items()}
    checks["failed_steps"] = {"value": failed, "limit": 0}
    checks["window_compiles"] = {"value": probe.window_compiles, "limit": 0}
    checks["final_params_finite"] = {"value": int(bool(probe.final_finite)),
                                     "limit": 1}
    result["checks"] = checks
    for line in lines:
        log(line)
    log(f"check failed_steps {failed} limit 0")
    log(f"check window_compiles {probe.window_compiles} limit 0")
    log(f"check final_params_finite {int(bool(probe.final_finite))} limit 1")
    return result


def _busy(rec: RunRecord) -> float:
    """Seconds in which some op ran, averaged over the cell's chips."""
    import devtrace as tr
    lo, hi = rec.trace_window
    per = [tr.busy_ns(ops, lo, hi) for ops in rec.trace.ops.values()]
    return sum(per) / max(len(per), 1) / 1e9


def breakdown(rec: RunRecord) -> dict:
    """The ten device ops that took most time (summed over the cell's
    chips; ops that only contain others left out) and the ten longest idle
    stretches: each idle gap of each chip split by the innermost benchmark
    span open on the host, and named ``<span>@chip<n>``."""
    import devtrace as tr
    lo, hi = rec.trace_window
    totals: dict[str, float] = {}
    pieces: list[tuple[str, float]] = []
    for chip, ops in sorted(rec.trace.ops.items()):
        for name, ns in tr.time_by_name(
                ops, lo, hi, lambda n: not tr.is_container(n)).items():
            key = tr.short(name)
            totals[key] = totals.get(key, 0.0) + ns
        for gap in sorted(tr.gaps(tr.busy(ops, lo, hi), lo, hi),
                          key=lambda g: g[0] - g[1])[:10]:
            pieces += [(f"{name}@chip{chip}", ns) for name, ns
                       in tr.split_gap(gap, rec.trace.spans).items()]
    top = sorted(totals.items(), key=lambda kv: -kv[1])[:10]
    pieces = sorted(pieces, key=lambda p: -p[1])[:10]
    return {"device_ops": [[n, ns / 1e9] for n, ns in top],
            "idle_gaps": [[n, ns / 1e9] for n, ns in pieces]}
