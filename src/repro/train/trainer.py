"""Sequential / data-parallel SSL training for the paper's experiments.

Reproduces the paper's §3 protocol: AdaGrad, base lr 1e-3, effective lr
``1e-3·k`` reset after 10 epochs, dropout 0.2, batch size 1024/2048, label
ratios 2–100%.  The same entry point drives the fully-supervised baseline
(γ=κ=0), the random-batch baseline, and the meta-batch method — only the
pipeline and hyper-parameters change.

``train_dnn_ssl`` is a thin wrapper over the unified scan-compiled
:class:`repro.train.engine.Engine`: it builds the :class:`TrainState`
and the Eq.-3 step/grad functions, picks an execution strategy
(``sequential`` / ``sync_mesh`` / ``async_ps`` — STRATEGY registry names),
and delegates the loop (scan compilation, buffer donation, host→device
prefetch, periodic checkpointing with exact resume) to the engine.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Iterable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.ssl_loss import SSLHyper
from repro.models.dnn import DNNConfig, dnn_forward, init_dnn
from repro.optim import Optimizer, adagrad, constant_lr, parallel_lr_schedule
from repro.train.engine import Engine, TrainState, data_mesh
from repro.train.train_step import dnn_ssl_grads, dnn_ssl_step

__all__ = ["TrainResult", "train_dnn_ssl", "evaluate_dnn"]


@dataclasses.dataclass
class TrainResult:
    params: dict
    history: list[dict]          # per-epoch metrics
    state: Any = None            # final engine TrainState (params/opt/rng/step)


def evaluate_dnn(params, X: np.ndarray, y: np.ndarray,
                 batch: int = 4096) -> float:
    correct = 0
    fwd = jax.jit(lambda p, x: jnp.argmax(dnn_forward(p, x), axis=-1))
    for s in range(0, len(X), batch):
        pred = fwd(params, jnp.asarray(X[s : s + batch]))
        correct += int((np.asarray(pred) == y[s : s + batch]).sum())
    return correct / len(X)


def train_dnn_ssl(
    pipeline_epoch: Callable[[], Iterable],
    *,
    cfg: DNNConfig,
    hyper: SSLHyper,
    n_epochs: int = 10,
    n_workers: int = 1,
    base_lr: float = 1e-3,
    lr_reset_epochs: int = 10,
    dropout: float = 0.2,
    eval_data: tuple[np.ndarray, np.ndarray] | None = None,
    eval_fn: Callable[[Any], dict] | None = None,
    seed: int = 0,
    opt: Optimizer | None = None,
    pairwise: str | Callable | None = "auto",
    mesh: jax.sharding.Mesh | None = None,
    strategy: str | None = None,
    scan_chunk: int = 16,
    prefetch: int = 2,
    max_staleness: int = 2,
    checkpoint_every: int = 0,
    checkpoint_dir: str | None = None,
    resume: bool = False,
    lr_schedule: Callable[[int], float] | None = None,
    params: dict | None = None,
    resilience=None,
    injector=None,
    capture_fn: Callable | None = None,
    capture_epochs: Callable[[int], bool] | Any = None,
    on_epoch_end: Callable[[int, Any, Any], None] | None = None,
) -> TrainResult:
    """Run the paper's training loop over ``pipeline_epoch`` batches.

    ``pairwise`` selects the Σ W_ij·Hc(p_i,p_j) implementation by PAIRWISE
    registry name — the default ``"auto"`` uses the fused Pallas kernel on
    TPU and the jnp oracle elsewhere — or is an already-resolved callable.

    ``strategy`` names a STRATEGY registry entry; when omitted it is
    inferred: ``"sync_mesh"`` if ``mesh`` (a ``("data",)`` mesh) is given —
    parameters replicated, each batch's leading worker axis sharded over it,
    the paper's k-worker synchronous SGD, each device computing its
    workers' losses under ``shard_map`` and all-reducing the gradient as the
    parameter server did — else ``"sequential"``.
    ``"async_ps"`` runs the §4 stale-gradient regime (``max_staleness``
    server steps of lag, dropout off — the async server pushes no rng).

    ``scan_chunk`` steps are compiled into one donated ``lax.scan`` (0 =
    the whole epoch — fastest, but the full epoch's batches are staged at
    once; the bounded default keeps host/device memory flat at big shapes);
    ``prefetch`` chunks are staged host→device ahead of compute.  ``checkpoint_every``/``checkpoint_dir`` enable periodic
    checkpoints; ``resume=True`` restores the newest one exactly (rng and
    step included).  ``params`` overrides the seeded init (back-compat for
    callers that pre-initialize).

    ``resilience`` (a ``ResilienceConfig``) turns on the engine's failure
    defenses — non-finite guard, checkpoint integrity/retention, prefetch
    supervision, async over-stale dropping; ``injector`` (a
    ``repro.resilience.FaultInjector``) arms deterministic fault injection
    for chaos testing.

    ``capture_fn(params, batch) -> array`` taps per-step embeddings inside
    the scan on epochs selected by ``capture_epochs``;
    ``on_epoch_end(epoch, params, captures)`` receives them stacked on
    host — the online graph-refresh hook (see ``repro.online``).
    """
    opt = opt or adagrad()
    key = jax.random.PRNGKey(seed)
    key, init_key = jax.random.split(key)
    if params is None:
        params = init_dnn(cfg, init_key)
    state = TrainState.create(params, opt.init(params), key)

    if strategy is None:
        strategy = "sync_mesh" if mesh is not None else "sequential"
    if strategy == "sync_mesh" and mesh is None:
        mesh = data_mesh(n_workers)
    if strategy == "async_ps" and dropout > 0.0:
        # The async server pushes no per-step rng to workers, so dropout
        # cannot be honored there — refuse rather than silently train a
        # different model than the caller configured.
        raise ValueError(
            "strategy 'async_ps' does not support dropout (the stale-"
            f"gradient workers are rng-free); got dropout={dropout}. "
            "Set dropout=0.0 explicitly.")

    # Resolve the pairwise kernel once; everything below passes the callable.
    from repro.api.registry import resolve_pairwise
    pairwise = resolve_pairwise(pairwise)
    worker_mesh = mesh if strategy == "sync_mesh" else None

    def step_fn(s: TrainState, batch: dict, lr):
        # Same split order as the historical Python loop: carry keeps the
        # first subkey, the step consumes the second — bit-identical stream.
        rng, sub = jax.random.split(s.rng)
        p, o, metrics = dnn_ssl_step(
            s.params, s.opt_state, batch, cfg=cfg, hyper=hyper, opt=opt,
            lr=lr, dropout_rng=sub, dropout=dropout, pairwise=pairwise,
            mesh=worker_mesh)
        return TrainState(params=p, opt_state=o, rng=rng,
                          step=s.step + 1), metrics

    def grad_fn(p, batch):  # async_ps: gradient at a (stale) snapshot
        return dnn_ssl_grads(p, batch, cfg=cfg, hyper=hyper,
                             dropout_rng=None, dropout=0.0,
                             pairwise=pairwise)

    engine = Engine(step_fn, grad_fn=grad_fn, opt=opt, strategy=strategy,
                    mesh=mesh, n_workers=n_workers,
                    max_staleness=max_staleness, scan_chunk=scan_chunk,
                    prefetch=prefetch, checkpoint_every=checkpoint_every,
                    checkpoint_dir=checkpoint_dir, resilience=resilience,
                    injector=injector, capture_fn=capture_fn)
    # The lr·k scaling rule compensates k-way gradient *averaging*; the
    # async server applies every pushed gradient individually, so its
    # reference regime keeps the base lr.
    schedule = lr_schedule or (
        constant_lr(base_lr) if strategy == "async_ps"
        else parallel_lr_schedule(base_lr, n_workers, lr_reset_epochs))
    if eval_fn is None and eval_data is not None:
        def eval_fn(p):
            return {"eval/acc": evaluate_dnn(jax.device_get(p), *eval_data)}
    res = engine.run(pipeline_epoch, state=state, n_epochs=n_epochs,
                     lr_schedule=schedule, eval_fn=eval_fn, resume=resume,
                     capture_epochs=capture_epochs, on_epoch_end=on_epoch_end)
    return TrainResult(params=res.state.params, history=res.history,
                       state=res.state)
