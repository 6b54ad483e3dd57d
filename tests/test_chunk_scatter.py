"""Each step's affinity block W travels from the pipeline to the engine as
its nonzero entries (``SparseBlock``) and is written once, by a scatter
into the engine's chunk buffer: the chunk handed to ``place_batch`` is the
one dense blocks would have built, bit for bit."""
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.api import (BatchConfig, DataConfig, ExecutionConfig, Experiment,
                       ExperimentConfig, ObjectiveConfig, RepartitionConfig,
                       TrainConfig)
from repro.api.registry import STRATEGY
from repro.core.affinity import SparseBlock
from repro.core.metabatch import block_layout
from repro.data.pipeline import MetaBatchPipeline, MetaBatchStream
from repro.train.engine import _as_host_dict, _stack_chunk

EPOCHS, CHUNK = 2, 2


def config(k: int) -> ExperimentConfig:
    """A tiny stream run, k workers, replanning every epoch."""
    return ExperimentConfig(
        data=DataConfig(n=800, n_classes=6, input_dim=32, manifold_dim=5,
                        label_ratio=0.1),
        batch=BatchConfig(batch_size=96, pipeline="metabatch_stream",
                          pad_headroom=2.0),
        repartition=RepartitionConfig(every_n_epochs=1, seed=3),
        objective=ObjectiveConfig(gamma=0.5, kappa=1e-4, weight_decay=1e-5,
                                  pairwise="ref"),
        train=TrainConfig(n_epochs=EPOCHS, dropout=0.1, base_lr=5e-3,
                          hidden_dim=32, n_hidden=1, n_workers=k,
                          execution="parallel" if k > 1 else "sequential"),
        execution=ExecutionConfig(scan_chunk=CHUNK, prefetch=2))


def dense_step(corpus, graph, idxs, P) -> dict:
    """One step's fields built the dense way, from ``graph.dense_block``."""
    def pad(a, dims):
        out = np.zeros((P,) * dims + a.shape[dims:], a.dtype)
        out[(slice(0, len(a)),) * dims] = a
        return out

    parts = [(pad(corpus.X[i], 1), pad(corpus.y[i], 1),
              pad(corpus.label_mask[i].astype(np.float32), 1),
              pad(graph.dense_block(i), 2), pad(np.ones(len(i), bool), 1))
             for i in idxs]
    return {name: np.stack(col) for name, col in
            zip(("x", "y", "label_mask", "W", "valid"), zip(*parts))}


def assert_bit_equal(got, want):
    got, want = np.asarray(got), np.asarray(want)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert got.tobytes() == want.tobytes()


@pytest.mark.parametrize("k", [1, 4])
def test_placed_chunk_equals_dense_blocks(k, monkeypatch):
    exp = Experiment(config(k)).build()
    stream = exp.pipeline.stream
    stream.record_indices = True
    pipe, indices = exp.pipeline, []

    def epoch_fn(epoch=None, n_epochs=None):
        yield from pipe(epoch=epoch, n_epochs=n_epochs)
        indices.append(stream.last_epoch_indices)

    exp.pipeline = epoch_fn
    placed = []
    cls = STRATEGY.get(exp._strategy())
    orig = cls.place_batch

    def place(self, chunk):
        placed.append(chunk)
        return orig(self, chunk)

    monkeypatch.setattr(cls, "place_batch", place)
    exp.run()
    assert stream.swaps == EPOCHS - 1      # the second epoch ran a new plan
    steps = [{name: a[t] for name, a in chunk.items()}
             for chunk in placed for t in range(len(chunk["W"]))]
    idxs = [step for epoch in indices for step in epoch]
    assert len(steps) == len(idxs) > 0
    for step, idx in zip(steps, idxs):
        assert len(idx) == k
        want = dense_step(exp.corpus, exp.graph, idx, stream.pad)
        assert set(step) == set(want)
        for name in want:
            assert_bit_equal(step[name], want[name])


def sparse_and_dense(k=2, P=64, seed=0):
    """A (k, P, P) block with a few entries, as a SparseBlock and dense."""
    rng = np.random.default_rng(seed)
    dense = np.zeros((k, P, P), np.float32)
    blocks = []
    for w in range(k):
        index = rng.choice(P * P, 40, replace=False)
        vals = rng.random(40).astype(np.float32) + 0.5
        dense[w].reshape(-1)[index] = vals
        blocks.append(SparseBlock((P, P), index, vals))
    return SparseBlock.stack(blocks), dense


def step(W, seed=0):
    k, P = W.shape[:2]
    x = np.random.default_rng(seed).random((k, P, 3)).astype(np.float32)
    return {"x": x, "W": W, "valid": np.ones((k, P), bool)}


def test_chunk_of_sparse_and_dense_steps():
    pairs = [sparse_and_dense(seed=s) for s in range(4)]
    steps = [step(sw if s % 2 else dw, s) for s, (sw, dw) in enumerate(pairs)]
    chunk = _stack_chunk(steps)
    assert_bit_equal(chunk["W"], np.stack([dw for _, dw in pairs]))
    assert_bit_equal(chunk["x"], np.stack([s["x"] for s in steps]))
    assert_bit_equal(chunk["valid"], np.stack([s["valid"] for s in steps]))


def test_asarray_gives_the_dense_block(small_graph_setup):
    corpus, graph, plan = small_graph_setup
    stream = MetaBatchStream(corpus, graph, plan, n_workers=2, seed=0,
                             record_indices=True)
    batches = list(stream.epoch(0))
    assert batches
    for batch, idxs in zip(batches, stream.last_epoch_indices, strict=True):
        assert isinstance(batch.W, SparseBlock)
        assert batch.W.shape == (2, stream.pad, stream.pad)
        want = dense_step(corpus, graph, idxs, stream.pad)["W"]
        assert_bit_equal(np.asarray(batch.W), want)
        assert_bit_equal(batch.W[1], want[1])


def test_host_dict_shares_the_batch_arrays(small_graph_setup):
    corpus, graph, plan = small_graph_setup
    batch = next(iter(MetaBatchPipeline(corpus, graph, plan, n_workers=2,
                                        seed=0).epoch()))
    host = _as_host_dict(batch)
    assert set(host) == {"x", "y", "label_mask", "W", "valid"}
    for name, a in host.items():
        assert a is getattr(batch, name)
    for name in ("x", "y", "label_mask", "valid"):
        assert np.shares_memory(host[name], getattr(batch, name))
    assert np.shares_memory(host["W"].vals, batch.W.vals)


@pytest.mark.parametrize("bt", [32, 64])
def test_layout_from_entries_equals_dense_layout(small_graph_setup, bt):
    corpus, graph, plan = small_graph_setup
    pipe = MetaBatchPipeline(corpus, graph, plan, n_workers=2, seed=0,
                             layout_bt=bt)
    names = ("tile_rows", "tile_cols", "tile_valid", "tile_crows",
             "tile_ccols", "tile_cvalid", "tile_occ")
    for batch in pipe.epoch():
        for w in range(2):
            want = block_layout(np.asarray(batch.W)[w], bt,
                                list_len=pipe.layout_len).arrays()
            for name, a in zip(names, want, strict=True):
                assert_bit_equal(getattr(batch, name)[w], a)
    # An entry stored with the value zero marks no tile, as in the dense W.
    P = pipe.pad
    block = SparseBlock((P, P), [0, P * (P - 1) + P - 1],
                        np.array([0.0, 1.0], np.float32))
    for got, want in zip(block_layout(block, bt).arrays(),
                         block_layout(np.asarray(block), bt).arrays(),
                         strict=True):
        assert_bit_equal(got, want)


def stack_stats(trace_dir) -> list[dict]:
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return [dict(e.stats) for plane in ProfileData.from_file(path).planes
            for line in plane.lines for e in line.events
            if e.name == "repro.engine.stack"]


@pytest.mark.parametrize("sparse_steps", [(1, 1, 1), (1, 0, 1), (0, 0, 0)])
def test_stack_span_counts_scattered_and_copied(tmp_path, sparse_steps):
    k = 3
    steps = []
    for s, sparse in enumerate(sparse_steps):
        sw, dw = sparse_and_dense(k=k, seed=s)
        steps.append(step(sw if sparse else dw, s))
    with jax.profiler.trace(str(tmp_path / "trace")):
        _stack_chunk(steps)
    n = sum(sparse_steps)
    assert stack_stats(str(tmp_path / "trace")) == [
        {"w_scattered": k * n, "w_copied": k * (len(sparse_steps) - n)}]
