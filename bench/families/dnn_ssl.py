"""The paper's DNN family (arXiv:1612.04898): ``input_dim -> n_hidden x
hidden_dim -> n_classes`` trained with the Eq.-3 graph regularizer, checked
against ``bench/references/dnn_ssl.py``.  What each name provides is in
``bench/families/__init__.py``."""
from __future__ import annotations

import functools

import numpy as np

import counts
import harness

#: The held-out eval call that the harness wraps in its ``eval`` span.
EVAL = ("repro.train.trainer", "evaluate_dnn")
#: The program's device scope of the DNN (forward; ``jvp`` / ``transpose``
#: of it in the backward pass).
MODEL_SCOPE = "repro.dnn"


def experiment_config(config: dict, traffic: dict, seeds: dict):
    """The ``ExperimentConfig`` of a configuration under a traffic mix."""
    from repro.api import (BatchConfig, DataConfig, ExecutionConfig,
                           ExperimentConfig, GraphConfig, ObjectiveConfig,
                           RepartitionConfig, TrainConfig)
    c, t, s = config, traffic, seeds
    return ExperimentConfig(
        name=c["name"],
        data=DataConfig(n=c["n_frames"], input_dim=c["input_dim"],
                        n_classes=c["n_classes"],
                        manifold_dim=c["manifold_dim"],
                        structure=c["structure"],
                        label_ratio=c["label_ratio"],
                        test_fraction=c["test_fraction"], seed=s["data"]),
        graph=GraphConfig(k=c["k_neighbours"],
                          construction=c["graph_construction"]),
        batch=BatchConfig(batch_size=t["batch_size"], pipeline=t["pipeline"],
                          with_neighbor=t["with_neighbor"],
                          layout_bt=t["layout_bt"]),
        repartition=RepartitionConfig(
            every_n_epochs=t["repartition_every_n_epochs"],
            seed=s["partition"]),
        objective=ObjectiveConfig(gamma=c["gamma"], kappa=c["kappa"],
                                  weight_decay=c["weight_decay"],
                                  pairwise=t["pairwise"]),
        train=TrainConfig(hidden_dim=c["hidden_dim"], n_hidden=c["n_hidden"],
                          dropout=c["dropout"], optimizer=c["optimizer"],
                          base_lr=c["base_lr"], n_workers=c["n_workers"],
                          n_epochs=t["max_epochs"], seed=s["model"]),
        execution=ExecutionConfig(strategy=c["strategy"],
                                  scan_chunk=t["scan_chunk"],
                                  prefetch=t["prefetch"]))


def warm_eval(exp) -> None:
    """The held-out eval jits anew on every call: warm its program once,
    with zero weights, so that the window's evals load it from the
    persistent cache."""
    import repro.train.trainer as trainer
    cfg = exp.config
    dims = ([cfg.data.input_dim] + [cfg.train.hidden_dim]
            * cfg.train.n_hidden + [cfg.data.n_classes])
    zeros = {"layers": [{"w": np.zeros((a, b), np.float32),
                         "b": np.zeros((b,), np.float32)}
                        for a, b in zip(dims[:-1], dims[1:])]}
    trainer.evaluate_dnn(zeros, *exp.eval_data)


def host_state(state) -> dict:
    """Params and AdaGrad accumulators as host ``(w, b)`` lists."""
    import jax
    params, accum = jax.device_get(
        (state.params["layers"], state.opt_state["accum"]["layers"]))
    return {"params": [(p["w"], p["b"]) for p in params],
            "accum": [(a["w"], a["b"]) for a in accum]}


def reference_numbers(cell, seed: int, first_chunk: dict, first: dict,
                      detail: bool = False, **variant) -> dict:
    """The program's first chunk (``first``: losses, regularizer values and
    states) against the reference over the same host batches; ``variant``
    (keyword arguments of the reference's ``run_steps``: a control's
    arithmetic or a ``fault``) puts the reference so computed in the
    program's place instead and compares that with the reference."""
    from correctness import compare
    ref = harness.load_module("references", cell.config["reference"])
    lr = cell.config["base_lr"] * cell.config["n_workers"]
    model_seed = harness.seeds_of(seed)["model"]
    ref_out = ref.run_steps(cell.config, model_seed, first_chunk, lr=lr)
    if variant:
        first = ref.run_steps(cell.config, model_seed, first_chunk, lr=lr,
                              **variant)
    return compare(first, ref_out, detail=detail)


def kernel_check(cell, seed: int, first_chunk: dict, **variant) -> dict:
    """The program's regularizer entry (``graph_regularizer`` under the
    cell's ``pairwise``: the kernels the timed step calls, at its shapes)
    on every block of the first chunk, against the reference's regularizer
    of the same log-probabilities (the reference's, at the seed's
    parameters) and masked block; ``variant`` (keyword arguments of the
    reference's ``regularizer_value_and_grad``) puts the reference so
    computed in the kernels' place instead."""
    import jax

    from correctness import kernel_numbers
    from repro.core.ssl_loss import graph_regularizer
    ref = harness.load_module("references", cell.config["reference"])
    cfg = cell.config
    hyper = {"gamma": cfg["gamma"], "kappa": cfg["kappa"]}
    program = jax.jit(jax.value_and_grad(functools.partial(
        graph_regularizer, pairwise=cell.traffic["pairwise"], **hyper)))
    model_seed = harness.seeds_of(seed)["model"]
    pairs = []
    S, k = first_chunk["x"].shape[:2]
    for t in range(S):
        for w in range(k):
            v = np.asarray(first_chunk["valid"][t, w], np.float32)
            W = jax.numpy.asarray(np.asarray(first_chunk["W"][t, w])
                                  * v[:, None] * v[None, :])
            logp = ref.seed_logp(cfg, model_seed, first_chunk["x"][t, w])
            want = ref.regularizer_value_and_grad(logp, W, **hyper)
            got = (ref.regularizer_value_and_grad(logp, W, **hyper,
                                                  **variant)
                   if variant else program(logp, W))
            pairs.append(jax.device_get((got, want)))
    return kernel_numbers(pairs)


def batch_check(cell, info: dict, first_chunk: dict) -> dict:
    """The first chunk's batches against the corpus and the reference's own
    k-NN graph of its features, and the replans the run owed."""
    from correctness import batch_numbers
    ref = harness.load_module("references", cell.config["reference"])
    corpus = info["corpus"]
    W_ref = ref.knn_rbf_graph(corpus.X, cell.config["k_neighbours"])
    out = batch_numbers(first_chunk, corpus.X, corpus.y, corpus.label_mask,
                        W_ref)
    out["replans_kept"] = info["replans_due"] - info["replans_swapped"]
    return out


def step_flops(config: dict, valid_rows) -> int:
    """Required FLOPs of one step: the DNN's forward and backward on each
    worker's valid rows (3 x 2 x MACs per row) plus the regularizer's
    3 x 2 n^2 C on each worker's ``n`` valid rows (``bench/counts.py``)."""
    c = config
    per_row = counts.dnn_train_flops_per_row(counts.dnn_dims(
        c["input_dim"], c["hidden_dim"], c["n_hidden"], c["n_classes"]))
    return sum(per_row * int(n) + counts.reg_train_flops(int(n),
                                                         c["n_classes"])
               for n in valid_rows)


def shrink(cell):
    """A cell cut to a size the CPU runs in seconds: its own limits and
    traffic kinds, a host-built graph, narrower layers.  Four 256-wide
    layers and a 16-step first chunk are the least at which the control
    (float8 matmul operands) drifts past the limits as it does at the
    paper's widths; at two 64-wide layers and two steps it does not."""
    cell.config.update(n_frames=4096, input_dim=32, hidden_dim=256,
                       n_hidden=4, n_classes=39, graph_construction="host")
    # The CPU multiplies float32 operands in float32, not in one bfloat16
    # pass as a TPU does at JAX's default precision.
    cell.config["dnn_operands"] = "float32"
    cell.traffic.update(batch_size=128, pad_rows=384)
    return cell
