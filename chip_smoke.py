"""Bring-up smoke test: graph-SSL training at the paper's widths on a TPU.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # the k=4 sync_mesh path, four chips

One chip runs four phases, in one process, through the repo's normal entry
point (``Experiment`` -> ``Engine`` -> PAIRWISE kernels):

  graph         the k=10 RBF graph's neighbour lists from the compiled Pallas
                top-k, checked on a seeded sample of rows against the host
                search;
  train         ``Experiment(cfg).run()`` on the paper's 351 -> 4x2000 -> 39
                DNN, 65,536 frames, 2,048-frame meta-batches re-partitioned
                every epoch, ``pairwise="auto"`` (the fused kernels on a TPU);
  blocksparse   the same run for one epoch with a 128-wide block layout, so
                ``"auto"`` takes the block-sparse kernels;
  kernel check  the regularizer and its logp gradient from the resolved
                PAIRWISE function against the ``"ref"`` oracle on a real batch
                of each of the two runs.

``--chips 4`` runs only the paper's k-worker path: a few ``sync_mesh`` steps
with four workers on a four-device mesh, and the same steps vmapped on one
device (``sequential``), whose parameters must agree.

Earlier lines print one JSON object per phase; their timings are
informational.  The last line is ``{"ok": true, "device": {...}}``, printed
only after every phase passed.  Without a TPU, or when any phase fails, the
last line is ``{"ok": false, ...}`` and the exit code is 1.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time
import traceback

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

# The paper's §3 setting (DNNConfig defaults, TIMIT's 351-d frames and 39
# phone classes), cut in corpus size only.
N_TRAIN = 65_536
INPUT_DIM = 351
N_CLASSES = 39
HIDDEN_DIM = 2000
N_HIDDEN = 4
BATCH_SIZE = 2048
K_NEIGHBOURS = 10
N_SAMPLE_ROWS = 1024
LAYOUT_BT = 128

#: Share of sampled neighbour lists on which the device and host searches
#: must agree; both are exact, so only f32 distance ties may differ.
MIN_NEIGHBOUR_AGREEMENT = 0.99
#: Kernel check against the "ref" oracle.  The regularizer is a difference
#: of two large positive sums (cross term minus degree-weighted entropy)
#: that nearly cancel once neighbours agree, so its error is bounded
#: relative to the summed magnitude of the two terms, not to the value.
#: The logp gradient: max-abs error over the gradient's max-abs.
VALUE_RTOL = 1e-5
GRAD_RTOL = 1e-3
#: --chips 4: per parameter leaf, |p_mesh - p_seq| / |p_seq - p_init|
#: (Frobenius norms).  The two runs differ only in f32 summation order (the
#: all-reduce against one vmapped contraction): ~5e-6 after one step.  At
#: 2000-wide layers each SGD step multiplies that difference by about
#: eight (all-f32 CPU run: 5.3e-6, 4.5e-5, 2.9e-4 after 1, 2, 4 steps), so
#: four steps at the paper's widths land near 2e-3.  A sharding fault (a
#: worker's shard dropped or counted twice, a wrong mean) is O(1).
MESH_UPDATE_RTOL = 1e-2


class SmokeFailure(RuntimeError):
    """A phase's output broke its contract."""


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def require_tpu():
    """The JAX devices, which must be TPUs; no fallback to another
    platform."""
    import jax
    devices = jax.devices()
    _check(devices[0].platform == "tpu",
           f"no TPU: JAX found {devices[0].platform!r} devices")
    return devices


def _peak_bytes(device) -> int | None:
    stats = device.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


# -------------------------------------------------------------------- config
def paper_config(*, n: int = N_TRAIN, hidden_dim: int = HIDDEN_DIM,
                 n_hidden: int = N_HIDDEN, batch_size: int = BATCH_SIZE,
                 n_epochs: int = 2, pairwise: str = "auto"):
    """The smoke's ``ExperimentConfig``: the paper's widths by default."""
    from repro.api import (BatchConfig, DataConfig, ExecutionConfig,
                           ExperimentConfig, GraphConfig, ObjectiveConfig,
                           RepartitionConfig, TrainConfig)
    return ExperimentConfig(
        name="chip_smoke",
        data=DataConfig(n=n, input_dim=INPUT_DIM, n_classes=N_CLASSES,
                        label_ratio=0.02),
        graph=GraphConfig(k=K_NEIGHBOURS, construction="device"),
        batch=BatchConfig(batch_size=batch_size, pipeline="metabatch_stream"),
        repartition=RepartitionConfig(every_n_epochs=1),
        objective=ObjectiveConfig(pairwise=pairwise),
        train=TrainConfig(hidden_dim=hidden_dim, n_hidden=n_hidden,
                          dropout=0.2, n_epochs=n_epochs),
        execution=ExecutionConfig(scan_chunk=16))


# -------------------------------------------------------------------- phases
def graph_phase(X: np.ndarray, *, k: int = K_NEIGHBOURS,
                n_sample: int = N_SAMPLE_ROWS, seed: int = 0) -> dict:
    """Device (Pallas) k-NN lists against the host search on a seeded
    sample of rows."""
    from repro.core.affinity import _streaming_topk_rows, knn_edges

    t0 = time.perf_counter()
    _, cols, _ = knn_edges(X, k, backend="device")
    device_s = time.perf_counter() - t0
    dev = cols.reshape(len(X), k)
    rows = np.sort(np.random.default_rng(seed).choice(
        len(X), size=min(n_sample, len(X)), replace=False))
    host, _ = _streaming_topk_rows(X[rows], X, k, 2048, 4096,
                                   self_of_row=rows)
    share = float(np.mean([len(set(dev[r]) & set(h)) / k
                           for r, h in zip(rows, host)]))
    rec = {"phase": "graph", "n": len(X), "k": k, "sample_rows": len(rows),
           "neighbour_agreement": share, "device_topk_s": device_s}
    _emit(rec)
    _check(share >= MIN_NEIGHBOUR_AGREEMENT,
           f"device/host neighbour agreement {share} < "
           f"{MIN_NEIGHBOUR_AGREEMENT}")
    return rec


def check_finite(name: str, history: list[dict]) -> None:
    """Every epoch's losses are finite."""
    _check(len(history) > 0, f"{name}: no epoch rows")
    for row in history:
        for key, val in row.items():
            if key.startswith("loss/"):
                _check(np.isfinite(val),
                       f"{name}: epoch {row['epoch']} {key}={val}")


def check_history(name: str, history: list[dict], n_classes: int) -> None:
    """Every epoch's losses are finite and the final held-out accuracy is
    above chance."""
    check_finite(name, history)
    acc = history[-1].get("eval/acc", 0.0)
    _check(acc > 1.0 / n_classes,
           f"{name}: eval/acc {acc} is not above chance 1/{n_classes}")


def build_phase(exp, *, name: str) -> None:
    """``exp.build()``: corpus, device-built graph, plan and stream."""
    t0 = time.perf_counter()
    exp.build()
    _emit({"phase": f"{name}/build", "setup_s": time.perf_counter() - t0,
           "n_meta": exp.plan.n_meta,
           "largest_meta_batch": max(len(m) for m in exp.plan.meta_batches),
           "graph_edges": exp.graph.n_edges})


def train_phase(exp, *, name: str):
    """``exp.run()``; returns the ``ExperimentResult``.  Checks nothing but
    prints the run's numbers."""
    import jax

    res = exp.run()
    hist = res.history
    cfg = exp.config
    steps = -(-exp.plan.n_meta // cfg.train.n_workers)
    rec = {"phase": name, "train_s": res.seconds,
           "steps_per_epoch": steps,
           "pad_rows": exp.pipeline.stream.pad,
           "replans_swapped": exp.pipeline.stream.swaps,
           "loss_per_epoch": [r["loss/total"] for r in hist],
           "eval_acc": hist[-1].get("eval/acc") if hist else None,
           "epoch_s": [r["seconds"] for r in hist],
           "peak_bytes_in_use": _peak_bytes(jax.devices()[0])}
    if len(hist) > 1:
        # Epoch 0 includes compilation; later epochs run the cached chunk.
        rec["steps_per_s"] = steps / hist[-1]["seconds"]
        rec["compile_s_approx"] = hist[0]["seconds"] - hist[-1]["seconds"]
    _emit(rec)
    return res


def check_loss_decreases(history: list[dict]) -> None:
    """The last epoch's mean loss (over its scan chunks) is below the
    first's."""
    first, last = history[0]["loss/total"], history[-1]["loss/total"]
    _check(len(history) > 1 and last < first,
           f"loss did not decrease: first epoch {first}, last epoch {last}")


def kernel_check(exp, params, *, name: str) -> dict:
    """Regularizer value and logp gradient of the resolved PAIRWISE function
    against the ``"ref"`` oracle (computed at "highest" matmul precision) on
    one real batch of ``exp``'s pipeline."""
    import jax
    import jax.numpy as jnp
    from repro.api import resolve_pairwise
    from repro.core.ssl_loss import (entropy, graph_regularizer,
                                     pairwise_cross_entropy_term)
    from repro.models.dnn import dnn_forward
    from repro.train.train_step import _TILE_KEYS

    cfg = exp.config
    batches = exp.pipeline(epoch=0, n_epochs=1)
    batch = next(iter(batches))
    batches.close()
    valid = jnp.asarray(batch.valid[0], jnp.float32)
    W = jnp.asarray(batch.W[0]) * valid[:, None] * valid[None, :]
    logp = jax.nn.log_softmax(dnn_forward(params, jnp.asarray(batch.x[0])),
                              axis=-1)
    layout = None
    if batch.tile_rows is not None:
        layout = tuple(jnp.asarray(getattr(batch, key)[0])
                       for key in _TILE_KEYS)
    gamma, kappa = cfg.objective.gamma, cfg.objective.kappa

    def value_and_grad(pairwise):
        """``((value, grad), whether a Mosaic kernel is in the program)``."""
        compiled = jax.jit(jax.value_and_grad(
            lambda lp, W, layout: graph_regularizer(
                lp, W, gamma, kappa, pairwise=pairwise, layout=layout))
        ).lower(logp, W, layout).compile()
        return (compiled(logp, W, layout),
                "tpu_custom_call" in compiled.as_text())

    impl = resolve_pairwise(cfg.objective.pairwise)
    (val, grad), mosaic = value_and_grad(impl)
    with jax.default_matmul_precision("highest"):
        (ref_val, ref_grad), _ = value_and_grad("ref")
        # The value is a difference of two large sums (cross term minus
        # degree-weighted entropy); their size bounds its rounding error.
        terms = float(gamma * pairwise_cross_entropy_term(logp, W)
                      + jnp.sum((kappa + gamma * W.sum(1)) * entropy(logp)))
    val, ref_val = float(val), float(ref_val)
    err = abs(val - ref_val)
    grad_scale = float(jnp.max(jnp.abs(ref_grad)))
    grad_err = float(jnp.max(jnp.abs(grad - ref_grad)))
    rec = {"phase": f"kernel_check[{name}]", "rows": int(W.shape[0]),
           "layout": layout is not None, "mosaic_kernel": mosaic,
           "value": val, "ref_value": ref_val,
           "value_rel_err": err / max(abs(ref_val), 1e-30),
           "summed_terms": terms, "err_over_summed_terms": err / terms,
           "value_rtol_of_terms": VALUE_RTOL,
           "grad_max_abs_err": grad_err, "grad_max_abs": grad_scale,
           "grad_rtol": GRAD_RTOL}
    _emit(rec)
    # On a TPU the kernels must run compiled: never interpreted, never the
    # oracle standing in for them.
    _check(mosaic or jax.default_backend() != "tpu",
           f"{name}: no compiled Pallas kernel in the regularizer program")
    _check(np.isfinite(val) and err <= VALUE_RTOL * terms,
           f"{name}: regularizer {val} vs ref {ref_val}: error {err} > "
           f"{VALUE_RTOL} x summed terms {terms}")
    _check(np.isfinite(grad_err) and grad_err <= GRAD_RTOL * grad_scale,
           f"{name}: logp-gradient error {grad_err} > "
           f"{GRAD_RTOL} x {grad_scale}")
    return rec


@contextlib.contextmanager
def recorded_placements(record: list):
    """Append, for every chunk the ``sync_mesh`` strategy places, each
    leaf's ``(addressable shards, distinct devices)``."""
    import jax
    from repro.train.engine import SyncMeshStrategy

    place = SyncMeshStrategy.place_batch

    def recording(self, chunk):
        placed = place(self, chunk)
        record.append([(len(a.addressable_shards),
                        len({s.device for s in a.addressable_shards}))
                       for a in jax.tree.leaves(placed)])
        return placed

    SyncMeshStrategy.place_batch = recording
    try:
        yield
    finally:
        SyncMeshStrategy.place_batch = place


def _initial_params(cfg):
    """The seeded initial parameters ``train_dnn_ssl`` starts from."""
    import jax
    from repro.models.dnn import DNNConfig, init_dnn
    t = cfg.train
    _, init_key = jax.random.split(jax.random.PRNGKey(t.seed))
    return init_dnn(DNNConfig(input_dim=cfg.data.input_dim,
                              hidden_dim=t.hidden_dim, n_hidden=t.n_hidden,
                              n_classes=cfg.data.n_classes,
                              dropout=t.dropout), init_key)


def mesh_phase(cfg, *, n_workers: int) -> dict:
    """``sync_mesh`` with ``n_workers`` workers on an ``n_workers``-device
    mesh against the same steps vmapped on one device (``sequential``)."""
    import jax
    from repro.api import Experiment
    from repro.train.engine import data_mesh

    # Plain SGD: AdaGrad's first step moves every weight by about ±lr
    # whatever its gradient's size, so last-bit differences in near-zero
    # gradient entries become lr-sized parameter differences.  SGD keeps
    # parameter differences proportional to gradient differences.
    def variant(strategy):
        return dataclasses.replace(
            cfg, train=dataclasses.replace(cfg.train, n_workers=n_workers,
                                           optimizer="sgd"),
            execution=dataclasses.replace(cfg.execution, strategy=strategy))

    seq = Experiment(variant("sequential"))
    build_phase(seq, name="mesh")
    # The same corpus, graph and initial plan: both runs see the same
    # batches in the same order.
    mesh = Experiment(variant("sync_mesh"), corpus=seq.corpus,
                      eval_data=seq.eval_data, graph=seq.graph,
                      plan=seq.plan)
    record: list = []
    # Full f32 matmuls in both runs, so that they differ only in summation
    # order.
    with jax.default_matmul_precision("highest"):
        res_seq = train_phase(seq, name=f"sequential_k{n_workers}")
        with recorded_placements(record):
            res_mesh = train_phase(mesh, name=f"sync_mesh_k{n_workers}")
    # Finite losses only: a few plain-SGD steps need not lift accuracy.
    for name, res in (("sequential", res_seq), ("sync_mesh", res_mesh)):
        check_finite(name, res.history)
    mesh_size = data_mesh(n_workers).devices.size
    leaves = {leaf for chunk in record for leaf in chunk}
    errs = []
    for a, b, c in zip(jax.tree.leaves(res_mesh.params),
                       jax.tree.leaves(res_seq.params),
                       jax.tree.leaves(_initial_params(cfg))):
        a, b, c = (np.asarray(jax.device_get(v), np.float64)
                   for v in (a, b, c))
        errs.append(float(np.linalg.norm(a - b)
                          / max(np.linalg.norm(b - c), 1e-30)))
    rec = {"phase": "mesh", "n_workers": n_workers, "mesh_size": mesh_size,
           "placed_chunks": len(record),
           "shards_and_devices_per_leaf": sorted(leaves),
           "param_devices": sorted({len(p.sharding.device_set) for p in
                                    jax.tree.leaves(res_mesh.params)}),
           "update_rel_err_per_leaf": errs,
           "max_update_rel_err": max(errs), "update_rtol": MESH_UPDATE_RTOL}
    _emit(rec)
    _check(mesh_size == n_workers,
           f"mesh has {mesh_size} devices, want {n_workers}")
    _check(bool(record) and leaves == {(n_workers, n_workers)},
           f"placed batches are not split into {n_workers} shards on "
           f"distinct devices: {sorted(leaves)}")
    _check(max(errs) <= MESH_UPDATE_RTOL,
           f"sync_mesh params differ from sequential: update rel err "
           f"{max(errs)} > {MESH_UPDATE_RTOL}")
    return rec


# ---------------------------------------------------------------------- main
def run_one_chip(cfg, *, n_sample: int = N_SAMPLE_ROWS,
                 layout_bt: int = LAYOUT_BT) -> None:
    """The graph, train, block-sparse and kernel-check phases."""
    from repro.api import Experiment

    exp = Experiment(cfg)
    build_phase(exp, name="train")
    graph_phase(exp.corpus.X, k=cfg.graph.k, n_sample=n_sample)
    res = train_phase(exp, name="train")
    check_history("train", res.history, cfg.data.n_classes)
    check_loss_decreases(res.history)
    kernel_check(exp, res.params, name="fused")

    # Same corpus, graph and initial plan, one epoch, with a block layout.
    bsp = Experiment(
        dataclasses.replace(
            cfg, batch=dataclasses.replace(cfg.batch, layout_bt=layout_bt),
            train=dataclasses.replace(cfg.train, n_epochs=1)),
        corpus=exp.corpus, eval_data=exp.eval_data, graph=exp.graph,
        plan=exp.plan)
    build_phase(bsp, name="blocksparse")
    bsp_res = train_phase(bsp, name="blocksparse")
    check_history("blocksparse", bsp_res.history, cfg.data.n_classes)
    kernel_check(bsp, bsp_res.params, name="blocksparse")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the k=4 sync_mesh path on four chips")
    args = ap.parse_args(argv)
    try:
        devices = require_tpu()
        sys.path.insert(0, os.path.join(ROOT, "src"))
        from repro.compile_cache import enable_compilation_cache
        enable_compilation_cache()
        if args.chips == 4:
            _check(len(devices) >= 4,
                   f"--chips 4 needs four devices, found {len(devices)}")
            # Half the corpus: 16 meta-batches, four k=4 steps.
            mesh_phase(paper_config(n=N_TRAIN // 2, n_epochs=1), n_workers=4)
        else:
            run_one_chip(paper_config())
    except Exception as e:  # noqa: BLE001 — every failure ends as ok: false
        traceback.print_exc()
        print(json.dumps({"ok": False, "error": f"{type(e).__name__}: {e}"}),
              flush=True)
        return 1
    dev = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": args.chips}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
