"""What decides ``correct``, at a size the CPU holds.

* The sound program passes the cell's own limits; each control (the
  reference in the program's place, in the step and in the regularizer
  kernels, below what the configuration states) fails them.
* A whole run (``harness.run_cell``: build, warm-up, window, check), with
  the look for a chip skipped and the timed path broken underneath, comes
  out ``correct: false`` for each fault a training cell can have: a step
  that returns its state unchanged, half of the batch left out (the mean
  over the rest), and on the k=4 cell the exchange between chips left out
  (in a child process with four CPU devices); and for each fault of the
  batches it trains on: an affinity block whose neighbour rows are paired
  with the wrong columns, a label altered, a replanned partition that is
  not swapped in.
"""
import contextlib
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

import harness
from calibrate import shift_neighbours
from conftest import BENCH_DIR, ROOT


def _first_chunk(cell, seed):
    harness.setup_jax()
    probe = harness.Probe(seconds=0.0, prefetch=0, trace_dir=None,
                          first_only=True)
    harness.drive(cell, seed, probe)
    return probe.first_chunk, probe.first


@pytest.mark.parametrize("seed", [5, 3_000_000_021])
def test_sound_program_passes_and_control_fails(tiny_cell, seed):
    from correctness import judge
    cell = tiny_cell()
    chunk, first = _first_chunk(cell, seed)
    limits = cell.limits["limits"]
    numbers = cell.family.reference_numbers(cell, seed, chunk, first)
    numbers.update(cell.family.kernel_check(cell, seed, chunk))
    ok, lines = judge({k: v for k, v in numbers.items() if k in limits},
                      {k: v for k, v in limits.items() if k in numbers})
    assert ok, lines
    ref = harness.load_module("references", cell.config["reference"])
    for name, kw in ref.CONTROLS.items():
        control = cell.family.reference_numbers(cell, seed, chunk, first,
                                                **kw["step"])
        control.update(cell.family.kernel_check(cell, seed, chunk,
                                                **kw["kernel"]))
        assert not judge({k: v for k, v in control.items() if k in limits},
                         {k: v for k, v in limits.items()
                          if k in control})[0], (name, control)


@contextlib.contextmanager
def broken_step(fault):
    """Plant ``fault`` in the program's training step."""
    import repro.train.trainer as trainer
    orig = trainer.dnn_ssl_step

    def step(params, opt_state, batch, **kw):
        if fault == "unchanged":
            _, _, metrics = orig(params, opt_state, batch, **kw)
            return params, opt_state, metrics
        if fault == "half_batch":
            valid = batch["valid"]
            rank = jnp.cumsum(valid.astype(jnp.int32), axis=-1)
            keep = rank <= jnp.sum(valid, -1, keepdims=True) // 2
            batch = dict(batch, valid=valid & keep)
            return orig(params, opt_state, batch, **kw)
        if fault == "no_exchange":
            # Worker 0's own gradient, as a chip that skipped the
            # all-reduce applies it; the loss reported stays the mean.
            _, _, metrics = orig(params, opt_state, batch, **kw)
            one = jax.tree.map(lambda a: a[:1], batch)
            p, o, _ = orig(params, opt_state, one, **dict(kw, mesh=None))
            return p, o, metrics
        raise ValueError(fault)

    trainer.dnn_ssl_step = step
    try:
        yield
    finally:
        trainer.dnn_ssl_step = orig


def _run(cell, seed):
    return harness.run_cell(cell, seed=seed, seconds=0.5, traced=False,
                            t_start=time.perf_counter())


def test_sound_run_is_correct(tiny_cell):
    res = _run(tiny_cell(), 3_000_000_023)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"frames_per_s", "setup_s"}


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_broken_step_is_not_correct(tiny_cell, fault):
    with broken_step(fault):
        res = _run(tiny_cell(), 3_000_000_029)
    assert not res["correct"], res["checks"]


@contextlib.contextmanager
def broken_batches(fault):
    """Plant ``fault`` in the batches the pipeline assembles, or in the
    swap of a replanned partition."""
    import repro.data.pipeline as pipeline
    orig_assemble = pipeline._assemble
    orig_fits = pipeline.MetaBatchStream._fits

    def assemble(corpus, graph, idx, P, **kw):
        x, y, mask, W, valid, *rest = orig_assemble(corpus, graph, idx, P,
                                                    **kw)
        if fault == "wrong_neighbours":
            W = shift_neighbours(W, len(idx))
        elif fault == "label_altered":
            y = y.copy()
            y[0] = (y[0] + 1) % corpus.n_classes
        return (x, y, mask, W, valid, *rest)

    if fault == "replan_kept":
        pipeline.MetaBatchStream._fits = lambda self, plan, graph: False
    else:
        pipeline._assemble = assemble
    try:
        yield
    finally:
        pipeline._assemble = orig_assemble
        pipeline.MetaBatchStream._fits = orig_fits


@pytest.mark.parametrize("fault", ["wrong_neighbours", "label_altered",
                                   "replan_kept"])
def test_broken_batches_are_not_correct(tiny_cell, fault):
    with broken_batches(fault):
        res = _run(tiny_cell(), 3_000_000_037)
    assert not res["correct"], res["checks"]


CHILD = """
import contextlib, json, os, sys, time
sys.path[:0] = [{bench!r}, {src!r}, {tests!r}]
import harness
from conftest import shrink
from test_correctness import broken_step
cell = shrink(harness.load_cell("dnn_timit_k4.b2048_replan"))
out = {{}}
for fault in (None, "no_exchange"):
    ctx = broken_step(fault) if fault else contextlib.nullcontext()
    with ctx:
        res = harness.run_cell(cell, seed=3000000031, seconds=0.5,
                               traced=False, t_start=time.perf_counter())
    out[str(fault)] = res["correct"]
print("RESULT " + json.dumps(out))
"""


def test_k4_exchange_left_out_is_not_correct(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    code = CHILD.format(bench=BENCH_DIR, src=os.path.join(ROOT, "src"),
                        tests=os.path.dirname(os.path.abspath(__file__)))
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    lines = [ln for ln in p.stdout.splitlines() if ln.startswith("RESULT ")]
    assert lines, p.stderr[-3000:]
    got = json.loads(lines[-1][len("RESULT "):])
    assert got == {"None": True, "no_exchange": False}
