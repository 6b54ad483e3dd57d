"""The program's own tracing (``repro.tracing``): the host spans a run
writes into a profiler trace, how they nest, that tracing leaves results
unchanged, and the device scopes of the chunk program."""
import collections
import contextlib
import dataclasses
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import tracing
from repro.api import (BatchConfig, DataConfig, ExecutionConfig, Experiment,
                       ExperimentConfig, ObjectiveConfig, RepartitionConfig,
                       ResilienceConfig, TrainConfig)

EPOCHS, K, CHUNK = 2, 2, 2

#: Each span's innermost enclosing ``repro.*`` span on its thread (None: no
#: enclosing span).  A span held open across a ``yield`` would enclose the
#: consumer's spans and show here as a parent no entry allows.
PARENTS = {
    "pipeline.densify": {"pipeline.block"},
    "replan.join": {"pipeline.epoch_begin"},
    "replan.synthesize": {None, "pipeline.epoch_begin"},
    "engine.metrics_fetch": {"engine.epoch_end"},
    "engine.eval": {"engine.epoch_end"},
    "engine.on_epoch_end": {"engine.epoch_end"},
    "engine.checkpoint": {"engine.epoch_end"},
    "build.corpus": {None}, "build.graph": {None}, "build.plan": {None},
    "build.pipeline": {None},
}
#: With prefetch 0 the chunk is produced inside the loop's wait.
SYNC_PARENTS = {"pipeline.epoch_begin", "pipeline.block", "pipeline.stack",
                "engine.to_host", "engine.stack", "engine.place"}


def config(tmp_path, *, prefetch: int, guarded: bool) -> ExperimentConfig:
    """A tiny stream run: replans every epoch, two-step chunks, k=2."""
    return ExperimentConfig(
        data=DataConfig(n=800, n_classes=6, input_dim=32, manifold_dim=5,
                        label_ratio=0.1),
        batch=BatchConfig(batch_size=96, pipeline="metabatch_stream",
                          pad_headroom=2.0),
        repartition=RepartitionConfig(every_n_epochs=1, seed=3),
        objective=ObjectiveConfig(gamma=0.5, kappa=1e-4, weight_decay=1e-5,
                                  pairwise="ref"),
        train=TrainConfig(n_epochs=EPOCHS, dropout=0.1, base_lr=5e-3,
                          hidden_dim=64, n_hidden=2, n_workers=K),
        execution=ExecutionConfig(
            scan_chunk=CHUNK, prefetch=prefetch,
            checkpoint_every=1 if guarded else 0,
            checkpoint_dir=str(tmp_path / "ckpt") if guarded else None),
        resilience=ResilienceConfig(nonfinite_guard=guarded, guard_window=2))


def program_spans(trace_dir) -> list[list[tuple]]:
    """Per thread line, its ``repro.*`` spans as (name, start, end, stats)."""
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    lines = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            spans = [(e.name[len("repro."):], e.start_ns,
                      e.start_ns + e.duration_ns, dict(e.stats))
                     for e in line.events if e.name.startswith("repro.")]
            if spans:
                lines.append(spans)
    return lines


def parents(spans: list[tuple]) -> list[tuple]:
    """(name, innermost enclosing span's name) per span of one line; a
    span that starts inside another and ends after it fails."""
    out, stack = [], []
    for name, s, e, _ in sorted(spans, key=lambda sp: (sp[1], -sp[2])):
        while stack and stack[-1][2] <= s:
            stack.pop()
        if stack:
            assert e <= stack[-1][2], (
                f"{name} [{s}, {e}] overlaps {stack[-1][0]} "
                f"[{stack[-1][1]}, {stack[-1][2]}] without nesting")
        out.append((name, stack[-1][0] if stack else None))
        stack.append((name, s, e))
    return out


@pytest.fixture(scope="module", params=["prefetch", "sync_guarded"])
def runs(request, tmp_path_factory):
    """One traced and one untraced run of the same configuration."""
    tmp = tmp_path_factory.mktemp(request.param)
    sync = request.param == "sync_guarded"
    cfg = config(tmp, prefetch=0 if sync else 2, guarded=sync)
    plain = Experiment(cfg).run()
    if sync:   # a fresh directory: the traced run must not resume
        cfg = dataclasses.replace(cfg, execution=dataclasses.replace(
            cfg.execution, checkpoint_dir=str(tmp / "ckpt_traced")))
    exp = Experiment(cfg)
    with jax.profiler.trace(str(tmp / "trace")):
        traced = exp.run()
    steps = exp.plan.n_meta // K + (exp.plan.n_meta % K > 0)
    return {"sync": sync, "plain": plain, "traced": traced,
            "lines": program_spans(str(tmp / "trace")), "steps": steps}


def test_spans_emitted_with_expected_counts(runs):
    spans = [sp for line in runs["lines"] for sp in line]
    count = collections.Counter(name for name, *_ in spans)
    steps = runs["steps"] * EPOCHS
    chunks = -(-runs["steps"] // CHUNK) * EPOCHS
    assert set(count) <= set(tracing.SPANS)
    want = {
        "build.corpus": 1, "build.graph": 1, "build.plan": 1,
        "build.pipeline": 1,
        "pipeline.epoch_begin": EPOCHS, "pipeline.block": steps * K,
        "pipeline.densify": steps * K, "pipeline.stack": steps,
        "engine.to_host": steps, "engine.stack": chunks,
        "engine.place": chunks, "engine.dispatch": chunks,
        # One wait per chunk, and one for the end of each epoch's stream.
        "engine.wait_chunk": chunks + EPOCHS,
        "engine.epoch_end": EPOCHS, "engine.metrics_fetch": EPOCHS,
        "engine.eval": EPOCHS,
        # Epoch 0 launches the replan for epoch 1, which collects it.
        "replan.synthesize": EPOCHS - 1, "replan.join": EPOCHS - 1,
    }
    if runs["sync"]:
        want.update({"engine.checkpoint": EPOCHS,
                     "engine.guard_fetch": chunks // 2})
    assert count == want
    joins = [stats for name, _, _, stats in spans if name == "replan.join"]
    assert all(stats == {"outcome": "swapped"} for stats in joins)
    assert {stats["steps"] for name, _, _, stats in spans
            if name == "engine.dispatch"} == {CHUNK}


def test_spans_nest_and_close_before_yield(runs):
    allowed = {**PARENTS}
    for name in tracing.SPANS:
        allowed.setdefault(name, {None})
    if runs["sync"]:
        for name in SYNC_PARENTS:
            allowed[name] = allowed[name] | {"engine.wait_chunk"}
        allowed["engine.place"] |= {"engine.wait_chunk"}
    for line in runs["lines"]:
        for name, parent in parents(line):
            assert parent in allowed[name], (name, parent)


def test_tracing_leaves_the_run_unchanged(runs):
    for a, b in zip(jax.tree.leaves(runs["plain"].params),
                    jax.tree.leaves(runs["traced"].params), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert [h["loss/total"] for h in runs["plain"].history] == \
        [h["loss/total"] for h in runs["traced"].history]


@pytest.mark.parametrize("outcome", ["swapped", "kept", "failed"])
def test_replan_join_records_its_outcome(tmp_path, outcome):
    exp = Experiment(config(tmp_path, prefetch=2, guarded=False)).build()
    stream = exp.pipeline.stream
    if outcome == "kept":          # the new plan does not fit the pinned pad
        stream._fits = lambda plan, graph: False
    if outcome == "failed":
        def fail(epoch):
            raise RuntimeError("replan failed")
        stream._synthesize = fail
    warns = (contextlib.nullcontext() if outcome == "swapped" else
             pytest.warns(UserWarning, match="keeping the previous plan"))
    with jax.profiler.trace(str(tmp_path / "trace")), warns:
        for epoch in range(EPOCHS):
            for _ in exp.pipeline(epoch=epoch, n_epochs=EPOCHS):
                pass
    joins = [stats for line in program_spans(str(tmp_path / "trace"))
             for name, _, _, stats in line if name == "replan.join"]
    assert joins == [{"outcome": outcome}]
    assert stream.swaps == (outcome == "swapped")


def test_parents_rejects_a_partial_overlap():
    assert parents([("a", 0, 10, {}), ("b", 2, 5, {})]) == [
        ("a", None), ("b", "a")]
    with pytest.raises(AssertionError, match="without nesting"):
        parents([("a", 0, 10, {}), ("b", 5, 12, {})])


def test_chunk_program_names_its_scopes():
    from repro.core.ssl_loss import SSLHyper
    from repro.models.dnn import DNNConfig, init_dnn
    from repro.optim import adagrad
    from repro.train.engine import Engine, TrainState
    from repro.train.train_step import dnn_ssl_step

    cfg, hyper, opt = DNNConfig(input_dim=8, hidden_dim=16, n_hidden=1,
                                n_classes=3), SSLHyper(), adagrad()

    def step_fn(s, batch, lr):
        p, o, m = dnn_ssl_step(s.params, s.opt_state, batch, cfg=cfg,
                               hyper=hyper, opt=opt, lr=lr, pairwise="ref")
        return dataclasses.replace(s, params=p, opt_state=o,
                                   step=s.step + 1), m

    engine = Engine(step_fn, scan_chunk=2)
    key = jax.random.PRNGKey(0)
    state = jax.eval_shape(lambda: TrainState.create(
        init_dnn(cfg, key), opt.init(init_dnn(cfg, key)), key))
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    batch = {"x": f32(2, 1, 16, 8), "y": jax.ShapeDtypeStruct(
        (2, 1, 16), jnp.int32), "label_mask": f32(2, 1, 16),
        "W": f32(2, 1, 16, 16), "valid": jax.ShapeDtypeStruct(
            (2, 1, 16), jnp.bool_)}
    text = engine._chunk_fn.lower(state, batch, f32(), False).as_text(
        debug_info=True)
    for name in tracing.SCOPES:
        assert name in text, name
