"""String-keyed component registries for the experiment layer.

Every pluggable stage of the paper's pipeline — affinity-graph construction,
balanced partitioning, batch synthesis, the pairwise Hc(p_i,p_j) kernel, and
the optimizer — is looked up by name here, in the style of the xFormers
factory pattern: configs carry *names*, registries map names to callables,
and new scenarios register a component instead of forking the wiring.

Default entries are **lazy** ``"module:attr"`` import specs, resolved (and
cached) on first :meth:`Registry.get`.  That keeps this module import-light
and lets low-level packages (``repro.core``, ``repro.train``) resolve names
through it without circular imports.

Registering a new component::

    from repro.api.registry import AFFINITY

    @AFFINITY.register("cosine_knn")
    def build_cosine_graph(X, *, k=10, **kw):
        ...

    # or, keeping the import lazy:
    AFFINITY.register("cosine_knn", "mypkg.graphs:build_cosine_graph")
"""
from __future__ import annotations

import functools
import importlib
from typing import Any, Callable, Iterable

__all__ = [
    "Registry",
    "AFFINITY",
    "AUDIT",
    "PARTITIONER",
    "PIPELINE",
    "PAIRWISE",
    "OPTIMIZER",
    "STRATEGY",
    "resolve_pairwise",
]


class Registry:
    """A named string→component table with lazy ``"module:attr"`` entries."""

    def __init__(self, kind: str):
        self.kind = kind
        self._entries: dict[str, Any] = {}

    # -- registration -----------------------------------------------------
    def register(self, name: str, component: Any = None):
        """Register ``component`` under ``name``.

        Usable three ways: directly (``reg.register("x", fn)``), with a lazy
        import spec (``reg.register("x", "pkg.mod:fn")``), or as a decorator
        (``@reg.register("x")``).  Re-registering a name overwrites it (so
        callers can shadow a default implementation).
        """
        if component is None:
            def deco(fn):
                self._entries[name] = fn
                return fn
            return deco
        self._entries[name] = component
        return component

    # -- lookup -----------------------------------------------------------
    def get(self, name: str) -> Any:
        """Resolve ``name``; raises ``KeyError`` listing known names."""
        if name not in self._entries:
            raise KeyError(
                f"unknown {self.kind} component {name!r}; "
                f"registered: {self.names()}")
        entry = self._entries[name]
        if isinstance(entry, str):  # lazy "module:attr" spec
            mod_name, _, attr = entry.partition(":")
            entry = getattr(importlib.import_module(mod_name), attr)
            self._entries[name] = entry
        return entry

    def names(self) -> list[str]:
        return sorted(self._entries)

    def __contains__(self, name: str) -> bool:
        return name in self._entries

    def __iter__(self) -> Iterable[str]:
        return iter(self.names())

    def __repr__(self) -> str:
        return f"Registry({self.kind!r}, names={self.names()})"


# --------------------------------------------------------------------------
# Default registries.  Specs are lazy so importing repro.api stays cheap.
# --------------------------------------------------------------------------

#: ``(X, *, k, sigma, ...) -> AffinityGraph``
AFFINITY = Registry("affinity")
AFFINITY.register("knn_rbf", "repro.core.affinity:build_affinity_graph")

#: ``(W, n_parts, *, tol, coarsen_to, seed) -> PartitionResult``
#:   * ``"multilevel"``      — the vectorized multilevel partitioner (also
#:     accepts ``temperature=`` for stochastic re-partitioning);
#:   * ``"multilevel_loop"`` — the seed per-node-loop implementation, kept
#:     as the quality/semantics reference.
PARTITIONER = Registry("partitioner")
PARTITIONER.register("multilevel", "repro.core.partition:partition_graph")
PARTITIONER.register("multilevel_loop",
                     "repro.core.partition:partition_graph_loop")

#: ``(corpus, graph, plan, *, n_workers, seed, ...) -> epoch_fn`` where
#: ``epoch_fn()`` yields device-ready ``SSLBatch``es for one epoch.
PIPELINE = Registry("pipeline")
PIPELINE.register("meta_batch", "repro.data.pipeline:make_meta_batch_pipeline")
PIPELINE.register("graph_batch",
                  "repro.data.pipeline:make_graph_batch_pipeline")
PIPELINE.register("random_batch",
                  "repro.data.pipeline:make_random_batch_pipeline")
#: ``"metabatch_stream"`` — the §2 stream as a first-class stage: meta-batch
#: pairs assembled on demand, with optional between-epoch stochastic
#: re-partitioning on a background thread (``RepartitionConfig``); its epoch
#: factory takes ``epoch=`` so scheduling survives checkpoint resume.
PIPELINE.register("metabatch_stream",
                  "repro.data.pipeline:make_metabatch_stream_pipeline")

#: ``(logp, W) -> scalar`` computing the Eq.-3/4 contraction
#: ``Σ_ij W_ij · Hc(p_i, p_j)`` — or, for entries carrying the
#: ``full_regularizer`` marker, ``(logp, W, γ, κ) -> scalar`` computing the
#: *entire* regularizer (cross + degrees + entropy) in one kernel sweep.
#:   * ``"ref"``    — the pure-jnp cross-term oracle (always available);
#:   * ``"pallas"`` — the MXU-tiled cross-term kernel with its tiled
#:     analytic VJP (interpret mode off-TPU);
#:   * ``"fused"``  — the single-pass fused regularizer kernel (fwd + tiled
#:     VJP), unconditionally Pallas;
#:   * ``"blocksparse"`` — the tile-skipping fused kernel driven by a
#:     ``BlockLayout`` (``layout=`` kwarg); falls back to ``"fused"`` when
#:     no layout is supplied;
#:   * ``"auto"``   — on TPU backends ``"blocksparse"`` when a layout is
#:     available, else ``"fused"``; the jnp oracle elsewhere.
PAIRWISE = Registry("pairwise")
PAIRWISE.register("ref", "repro.kernels.ref:graph_reg_pairwise_ref")
PAIRWISE.register("pallas", "repro.kernels.ops:graph_reg_pairwise_pallas_vjp")
PAIRWISE.register("fused", "repro.kernels.ops:graph_regularizer_fused")
PAIRWISE.register("blocksparse",
                  "repro.kernels.ops:graph_regularizer_blocksparse")
PAIRWISE.register("auto", "repro.kernels.ops:graph_regularizer_auto")

#: ``(engine) -> strategy`` execution strategies for the unified training
#: engine (:mod:`repro.train.engine`) — how the scan body maps work onto
#: devices:
#:   * ``"sequential"`` — single-device execution;
#:   * ``"sync_mesh"``  — params replicated over a ``("data",)`` mesh, each
#:     chunk's worker axis sharded over it (the paper's synchronous k-worker
#:     SGD, the gradient all-reduced across it);
#:   * ``"async_ps"``   — the §4 stale-gradient parameter-server simulation
#:     (snapshots + round-robin schedule inside the scan body).
STRATEGY = Registry("strategy")
STRATEGY.register("sequential", "repro.train.engine:SequentialStrategy")
STRATEGY.register("sync_mesh", "repro.train.engine:SyncMeshStrategy")
STRATEGY.register("async_ps", "repro.train.engine:AsyncPSStrategy")

#: Audited entry points of the static-analysis toolkit
#: (:mod:`repro.analysis`): each name resolves to a
#: ``repro.analysis.jaxpr_audit.EntryPoint`` — how to trace one compiled
#: surface and what contracts its jaxpr must satisfy.  The CLI
#: (``python -m repro.analysis``) audits every registered name; register a
#: new entry here to put a new compiled path under the CI gate.
AUDIT = Registry("audit")
AUDIT.register("graph_reg_fused", "repro.analysis.entrypoints:graph_reg_fused")
AUDIT.register("graph_reg_blocksparse",
               "repro.analysis.entrypoints:graph_reg_blocksparse")
AUDIT.register("graph_reg_ref", "repro.analysis.entrypoints:graph_reg_ref")
AUDIT.register("knn_topk", "repro.analysis.entrypoints:knn_topk")
AUDIT.register("online_refresh",
               "repro.analysis.entrypoints:online_refresh")
AUDIT.register("ssl_objective", "repro.analysis.entrypoints:ssl_objective")
AUDIT.register("engine_sequential",
               "repro.analysis.entrypoints:engine_sequential")
AUDIT.register("engine_sync_mesh",
               "repro.analysis.entrypoints:engine_sync_mesh")
AUDIT.register("engine_async_ps",
               "repro.analysis.entrypoints:engine_async_ps")
AUDIT.register("engine_capture",
               "repro.analysis.entrypoints:engine_capture")
AUDIT.register("serve_decode_generate",
               "repro.analysis.entrypoints:serve_decode_generate")

#: ``(**hyper) -> repro.optim.Optimizer``
OPTIMIZER = Registry("optimizer")
OPTIMIZER.register("adagrad", "repro.optim:adagrad")
OPTIMIZER.register("adam", "repro.optim:adam")
OPTIMIZER.register("sgd", "repro.optim:sgd")


def resolve_pairwise(
    pairwise: str | Callable | None,
    *,
    tiles=None,
) -> Callable | None:
    """Resolve a pairwise-kernel *name* to its implementation.

    ``None`` (use the caller's inline oracle) and already-resolved callables
    pass through unchanged, so call sites can accept either form.

    ``tiles`` (a ``repro.kernels.tuning.TileSpec``, e.g. from
    ``ObjectiveConfig.tiles()``) pins kernel block sizes: entries that
    advertise ``accepts_tiles`` are wrapped so every call carries the spec;
    entries that don't (the jnp oracle) ignore it.
    """
    if pairwise is None or callable(pairwise):
        return pairwise
    impl = PAIRWISE.get(pairwise)
    if tiles is not None and getattr(impl, "accepts_tiles", False):
        @functools.wraps(impl)   # copies full_regularizer/accepts_tiles too
        def tiled(*args, _impl=impl, _tiles=tiles, **kw):
            kw.setdefault("tiles", _tiles)
            return _impl(*args, **kw)
        return tiled
    return impl
