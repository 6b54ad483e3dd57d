"""The ``Experiment`` runner — one entry point for every paper scenario.

``Experiment(config).run()`` drives the whole pipeline from an
``ExperimentConfig``: corpus → affinity graph → balanced partition →
meta-batch synthesis → Eq.-3 objective → sequential or k-worker
data-parallel SGD — every stage resolved by name through the registries in
``repro.api.registry``.  The hand-wired entry points in ``examples/`` and
``benchmarks/`` are thin shells over this class.

Pre-built artifacts (a labeled corpus, a shared affinity graph, a reusable
meta-batch plan) can be injected through the constructor so sweeps — e.g.
the Fig.-3a label-ratio grid — don't re-run graph construction per point::

    exp = Experiment(cfg, corpus=labeled, eval_data=test,
                     graph=graph, plan=plan)
    result = exp.run()
"""
from __future__ import annotations

import dataclasses
import inspect
import time
from typing import Any, Callable

import numpy as np

from repro.api.config import ExperimentConfig
from repro.api.registry import (AFFINITY, OPTIMIZER, PARTITIONER, PIPELINE,
                                resolve_pairwise)
from repro.tracing import span

__all__ = ["Experiment", "ExperimentResult"]


@dataclasses.dataclass
class ExperimentResult:
    """Structured output of one :meth:`Experiment.run`."""

    config: ExperimentConfig
    history: list[dict]       # per-epoch metric rows from the trainer
    final: dict               # last epoch's row ({} if no epoch produced one)
    seconds: float            # wall-clock for the training loop
    params: Any = None        # trained model parameters (pytree)

    def best(self, key: str = "eval/acc") -> float:
        """Best value of ``key`` across epochs (e.g. peak test accuracy)."""
        vals = [h[key] for h in self.history if key in h]
        if not vals:
            raise KeyError(f"metric {key!r} not present in history")
        return max(vals)


class Experiment:
    """Config-driven experiment: ``build()`` assembles, ``run()`` trains."""

    def __init__(
        self,
        config: ExperimentConfig,
        *,
        corpus=None,
        eval_data: tuple[np.ndarray, np.ndarray] | None = None,
        graph=None,
        plan=None,
        hierarchy_cache=None,
        injector=None,
    ):
        self.config = config
        self.corpus = corpus          # SyntheticCorpus (labels already dropped)
        self.eval_data = eval_data    # (X_test, y_test) or None
        self.graph = graph            # AffinityGraph
        self.plan = plan              # MetaBatchPlan
        self.hierarchy_cache = hierarchy_cache  # shared HierarchyCache
        self.injector = injector      # repro.resilience.FaultInjector (chaos)
        self.pipeline: Callable | None = None   # epoch-factory callable
        self.online = None            # repro.online.OnlineManager when active
        self._built = False

    # ------------------------------------------------------------------ build
    def build(self) -> "Experiment":
        """Assemble corpus, graph, plan and batch pipeline (idempotent)."""
        if self._built:
            return self
        cfg = self.config
        if self.corpus is None:
            with span("build.corpus"):
                self.corpus, self.eval_data = self._make_data()
        if self.graph is None:
            builder = AFFINITY.get(cfg.graph.builder)
            # Only forward the construction backend to builders that take
            # it — custom AFFINITY entries keep the bare (X, k=, sigma=)
            # contract from the registry docs.
            try:
                params = inspect.signature(builder).parameters
                takes_backend = ("backend" in params or any(
                    p.kind is inspect.Parameter.VAR_KEYWORD
                    for p in params.values()))
            except (TypeError, ValueError):   # non-introspectable callable
                takes_backend = False
            if takes_backend:
                kw = {"backend": cfg.graph.construction}
            elif cfg.graph.construction != "host":
                raise ValueError(
                    f"graph.construction={cfg.graph.construction!r} but "
                    f"AFFINITY builder {cfg.graph.builder!r} does not "
                    f"accept a backend= argument")
            else:
                kw = {}
            with span("build.graph"):
                self.graph = builder(self.corpus.X, k=cfg.graph.k,
                                     sigma=cfg.graph.sigma, **kw)
        needs_plan = cfg.batch.pipeline != "random_batch"
        if self.plan is None and needs_plan:
            from repro.core.metabatch import plan_meta_batches
            with span("build.plan"):
                self.plan = plan_meta_batches(
                    self.graph, batch_size=cfg.batch.batch_size,
                    n_classes=self.corpus.n_classes, seed=cfg.data.seed,
                    tol=cfg.partition.tol,
                    shuffle_blocks=cfg.batch.shuffle_blocks,
                    partitioner=PARTITIONER.get(cfg.partition.method),
                    coarsen_to=cfg.partition.coarsen_to)
        factory = PIPELINE.get(cfg.batch.pipeline)
        # The async parameter-server regime consumes 1-worker batches
        # round-robin (k lives in the engine strategy, not the pipeline).
        pipeline_workers = (1 if self._strategy() == "async_ps"
                            else cfg.train.n_workers)
        # Extra keys are swallowed by factories that don't need them (the
        # uniform ``**_`` contract): the stream pipeline consumes the
        # re-partitioning config and the partition settings it re-runs with.
        with span("build.pipeline"):
            self.pipeline = factory(
                self.corpus, self.graph, self.plan,
                batch_size=cfg.batch.batch_size,
                n_workers=pipeline_workers,
                with_neighbor=cfg.batch.with_neighbor,
                pad_factor=cfg.batch.pad_factor,
                pad_headroom=cfg.batch.pad_headroom,
                seed=cfg.data.seed,
                repartition=cfg.repartition,
                partitioner=PARTITIONER.get(cfg.partition.method),
                tol=cfg.partition.tol,
                coarsen_to=cfg.partition.coarsen_to,
                shuffle_blocks=cfg.batch.shuffle_blocks,
                hierarchy_cache=self._hierarchy_cache(),
                supervisor=self._replan_supervisor(),
                fault_injector=self.injector,
                record_indices=cfg.online.active,
                layout_bt=cfg.batch.layout_bt)
            if cfg.online.active:
                self.online = self._make_online_manager()
        self._built = True
        return self

    def _make_online_manager(self):
        """The ``repro.online.OnlineManager`` bound to this experiment's
        stream: refreshes the affinity graph from captured embeddings every
        ``online.refresh_every`` epochs and serves :meth:`insert`/
        :meth:`evict` for dynamic corpora."""
        from repro.online import OnlineManager
        cfg = self.config
        return OnlineManager(
            self.pipeline.stream, self.corpus, self.graph, cfg.online,
            batch_size=cfg.batch.batch_size,
            n_classes=self.corpus.n_classes,
            tol=cfg.partition.tol, coarsen_to=cfg.partition.coarsen_to,
            shuffle_blocks=cfg.batch.shuffle_blocks,
            partitioner=PARTITIONER.get(cfg.partition.method),
            embed_fn=self._embed_fn(), seed=cfg.data.seed)

    def _embed_fn(self):
        """Chunked clean forward to the tapped hidden layer — fills capture
        gaps and embeds freshly inserted rows."""
        import jax
        import jax.numpy as jnp
        from repro.models.dnn import dnn_hidden
        tap = self.config.online.tap

        hidden = jax.jit(lambda p, x: dnn_hidden(p, x, layer=tap))

        def embed(params, X, batch: int = 4096):
            outs = [np.asarray(hidden(params, jnp.asarray(X[s: s + batch])))
                    for s in range(0, len(X), batch)]
            return np.concatenate(outs) if outs else np.empty((0, 0))
        return embed

    def _replan_supervisor(self):
        """Supervisor for the stream's replan builder (None when retries
        are configured off — the stream then degrades on first failure).
        Uses ``replan_hang_timeout``, not the prefetch ``hang_timeout``:
        a real re-synthesis takes far longer than a device-put."""
        r = self.config.resilience
        if r.max_retries <= 0:
            return None
        from repro.resilience.supervisor import RetryPolicy, Supervisor
        return Supervisor(RetryPolicy(
            max_retries=r.max_retries, backoff_base=r.backoff_base,
            backoff_max=r.backoff_max, hang_timeout=r.replan_hang_timeout,
            seed=r.seed), name="replan")

    def _hierarchy_cache(self):
        """``HierarchyCache`` for hierarchy-reuse replans: the injected one
        when the constructor got ``hierarchy_cache=`` (sweeps over one
        shared graph pass the same cache so the coarsening chain is built
        once across all points), otherwise built fresh for this
        experiment.  ``None`` when re-partitioning is off, reuse is
        disabled, or the configured partitioner can't honor it (the
        stream then replans from scratch)."""
        cfg = self.config
        if not (cfg.repartition.active and cfg.repartition.reuse_hierarchy):
            return None
        from repro.introspect import accepts_kwarg
        if not accepts_kwarg(PARTITIONER.get(cfg.partition.method), "reuse"):
            return None
        if self.hierarchy_cache is not None:
            return self.hierarchy_cache
        from repro.core.partition import HierarchyCache
        return HierarchyCache(
            self.graph.W, tol=cfg.partition.tol,
            coarsen_to=cfg.partition.coarsen_to,
            seed=cfg.repartition.seed)

    def _strategy(self) -> str:
        """Effective STRATEGY name: an explicit ``ExecutionConfig.strategy``
        always wins; ``None`` falls back to the legacy
        ``TrainConfig.execution`` shorthand ("parallel" → "sync_mesh")."""
        strategy = self.config.execution.strategy
        if strategy is None:
            strategy = ("sync_mesh"
                        if self.config.train.execution == "parallel"
                        else "sequential")
        return strategy

    def _make_data(self):
        """Synthesize the train corpus + held-out test split from the config."""
        from repro.data import drop_labels, make_corpus

        d = self.config.data
        n_total = d.n + int(round(d.n * d.test_fraction))
        full = make_corpus(n_total, n_classes=d.n_classes,
                           input_dim=d.input_dim,
                           manifold_dim=d.manifold_dim,
                           structure=d.structure, seed=d.seed)
        train = dataclasses.replace(
            full, X=full.X[: d.n], y=full.y[: d.n],
            label_mask=full.label_mask[: d.n])
        eval_data = ((full.X[d.n:], full.y[d.n:])
                     if n_total > d.n else None)
        if d.label_ratio < 1.0:
            train = drop_labels(train, d.label_ratio, seed=d.seed + 1)
        return train, eval_data

    # -------------------------------------------------------------------- run
    def run(self) -> ExperimentResult:
        """Train end to end and return the structured result."""
        self.build()
        from repro.models.dnn import DNNConfig
        from repro.train.trainer import train_dnn_ssl

        cfg = self.config
        t = cfg.train
        ex = cfg.execution
        model_cfg = DNNConfig(
            input_dim=self.corpus.X.shape[1], hidden_dim=t.hidden_dim,
            n_hidden=t.n_hidden, n_classes=self.corpus.n_classes,
            dropout=t.dropout)
        strategy = self._strategy()
        # Resolve the pairwise kernel once here (with any pinned tile sizes
        # from the config) and hand the callable down — nothing below this
        # point touches the registry again.  A pipeline-built block layout
        # fixes the kernel's square tile edge: pin bi to layout_bt so the
        # block-sparse kernel's grid matches the layout the batches carry
        # (config validation already rejects a conflicting tile_bi).
        tiles = cfg.objective.tiles()
        if cfg.batch.layout_bt is not None:
            from repro.kernels.tuning import TileSpec
            tiles = tiles or TileSpec()
            if tiles.bi is None:
                tiles = dataclasses.replace(tiles, bi=cfg.batch.layout_bt)
        pairwise = resolve_pairwise(cfg.objective.pairwise, tiles=tiles)
        capture_fn = capture_epochs = on_epoch_end = None
        if self.online is not None:
            from repro.models.dnn import dnn_hidden
            import jax
            tap = cfg.online.tap

            def capture_fn(params, batch):
                # batch["x"] is (k_workers, P, d); tap the hidden layer
                # per worker row — stacked by the scan into (steps, k, P, H).
                return jax.vmap(
                    lambda xb: dnn_hidden(params, xb, layer=tap))(batch["x"])

            capture_epochs = self.online.capture_epoch
            on_epoch_end = self.online.on_epoch_end
        t0 = time.perf_counter()
        res = train_dnn_ssl(
            self.pipeline,
            cfg=model_cfg,
            hyper=cfg.objective.hyper(),
            n_epochs=t.n_epochs,
            n_workers=t.n_workers,
            base_lr=t.base_lr,
            lr_reset_epochs=t.lr_reset_epochs,
            dropout=t.dropout,
            eval_data=self.eval_data,
            seed=t.seed,
            opt=OPTIMIZER.get(t.optimizer)(),
            pairwise=pairwise,
            strategy=strategy,
            scan_chunk=ex.scan_chunk,
            prefetch=ex.prefetch,
            max_staleness=ex.max_staleness,
            checkpoint_every=ex.checkpoint_every,
            checkpoint_dir=ex.checkpoint_dir,
            resume=ex.resume,
            resilience=cfg.resilience,
            injector=self.injector,
            capture_fn=capture_fn,
            capture_epochs=capture_epochs,
            on_epoch_end=on_epoch_end)
        seconds = time.perf_counter() - t0
        final = res.history[-1] if res.history else {}
        return ExperimentResult(config=cfg, history=res.history,
                                final=final, seconds=seconds,
                                params=res.params)
