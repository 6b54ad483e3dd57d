"""Unified scan-compiled training engine (the one loop for every scenario).

One :class:`Engine` replaces the three divergent Python-stepped loops the
repo grew (sequential trainer, async parameter-server simulation, launcher
smoke path).  It compiles a whole epoch — or fixed-size chunks of steps —
into a single jitted ``lax.scan`` whose carry (:class:`TrainState`) is
**donated**, so per-step Python dispatch and per-step state copies both
disappear from the hot loop, and feeds the scan from a double-buffered
host→device prefetch iterator so the next chunk is stacked and transferred
while the current one computes.

How work is mapped onto devices is an *execution strategy*, looked up by
name in the ``repro.api.registry.STRATEGY`` registry:

  * ``"sequential"`` — single-device execution (state and batches on the
    default device);
  * ``"sync_mesh"``  — the paper's k-worker synchronous SGD: parameters
    replicated over a ``("data",)`` mesh, each chunk's worker axis sharded
    over it, each device computing its workers' losses under ``shard_map``
    and the gradient all-reduce standing in for the parameter server;
  * ``"async_ps"``   — the §4 stale-gradient parameter-server simulation:
    each of k workers holds a snapshot up to ``max_staleness`` server steps
    old, gradients are taken at the snapshot and applied to the live
    parameters immediately (deterministic round-robin schedule, expressed
    entirely inside the scan body).

Periodic checkpointing (``checkpoint_every`` epochs into ``checkpoint_dir``)
saves the *strategy carry* — params, optimizer state, rng key, step counter,
and for async the snapshots/ages too — so ``run(..., resume=True)`` resumes
mid-run exactly: the restored run's history matches an uninterrupted run.
Host-side pipeline RNG is replayed by draining the skipped epochs' batch
iterators (data pass only, no compute).
"""
from __future__ import annotations

import dataclasses
import functools
import json
import math
import os
import queue
import threading
import time
import warnings
from collections import deque
from typing import Any, Callable, Iterable, Iterator

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.affinity import SparseBlock
from repro.introspect import accepts_kwarg
from repro.resilience.guard import NonFiniteHaltError, all_finite, guard_init
from repro.resilience.supervisor import Supervisor
from repro.tracing import scope, span
from repro.train.checkpoint import (atomic_write_text, load_checkpoint,
                                    save_checkpoint)

__all__ = [
    "TrainState",
    "EngineResult",
    "Engine",
    "MESH_AXIS",
    "data_mesh",
    "lift_step",
    "prefetch_to_device",
    "SequentialStrategy",
    "SyncMeshStrategy",
    "AsyncPSStrategy",
]

_LATEST = "LATEST"


# --------------------------------------------------------------------- state
@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["params", "opt_state", "rng", "step"],
                   meta_fields=[])
@dataclasses.dataclass
class TrainState:
    """The scan carry: everything a training step reads and writes.

    Pytree-registered so it flows through ``jit``/``scan``/``device_put``
    and checkpoints as a flat tree.  ``rng`` and ``step`` live *inside* the
    state so a restored checkpoint resumes the exact dropout stream and
    worker schedule.
    """

    params: Any
    opt_state: Any
    rng: jax.Array           # PRNG key consumed by the step (dropout etc.)
    step: jax.Array          # global step counter (int32 scalar)

    @classmethod
    def create(cls, params, opt_state, rng) -> "TrainState":
        return cls(params=params, opt_state=opt_state, rng=rng,
                   step=jnp.zeros((), jnp.int32))


@dataclasses.dataclass
class EngineResult:
    state: TrainState
    history: list[dict]      # per-epoch metric rows

    @property
    def params(self):
        return self.state.params


def lift_step(update_fn: Callable) -> Callable:
    """Adapt a raw ``(params, opt_state, batch, lr) -> (params, opt_state,
    metrics)`` update into an engine ``step_fn``: threads the step counter,
    leaves ``rng`` untouched (for rng-free steps like the LM path — steps
    that consume rng write their own adapter, as the SSL trainer does)."""

    def step_fn(state: TrainState, batch, lr):
        params, opt_state, metrics = update_fn(state.params, state.opt_state,
                                               batch, lr)
        return dataclasses.replace(state, params=params, opt_state=opt_state,
                                   step=state.step + 1), metrics

    return step_fn


#: The one mesh axis the training engine shards over.  Every collective
#: a strategy introduces must bind this name — it is the axis the S-pass
#: (``repro.analysis.sharding_audit``) checks the engine entry points'
#: declared ``EntryPoint.mesh_axes`` against.
MESH_AXIS = "data"


def data_mesh(n_workers: int):
    """``(MESH_AXIS,)`` mesh whose size is the largest divisor of
    ``n_workers`` realizable on the available devices (1 on a
    single-device host — the sharded arrays then simply live on that
    device).  The axis is ``AxisType.Auto``: the strategy's shardings are
    placement hints for the partitioner, so traced values keep unsharded
    types and ``vmap``/``value_and_grad`` see plain arrays."""
    n_dev = len(jax.devices())
    size = max(d for d in range(1, min(n_workers, n_dev) + 1)
               if n_workers % d == 0)
    return jax.make_mesh((size,), (MESH_AXIS,),
                         axis_types=(jax.sharding.AxisType.Auto,))


# ------------------------------------------------------------------ prefetch
def prefetch_to_device(chunks: Iterable, put: Callable, depth: int = 2
                       ) -> Iterator:
    """Double-buffered host→device pipeline: a background thread stacks and
    transfers up to ``depth`` chunks ahead of the consumer, so host work and
    H2D copies overlap device compute.  ``depth <= 0`` degrades to a plain
    synchronous map (useful for debugging)."""
    if depth <= 0:
        it, end = iter(chunks), object()
        while True:
            with span("engine.wait_chunk"):
                c = next(it, end)
                if c is end:
                    return
                with span("engine.place"):
                    placed = put(c)
            yield placed
    q: queue.Queue = queue.Queue(maxsize=depth)
    sentinel = object()
    stop = threading.Event()
    errors: list[BaseException] = []

    def _put(item) -> bool:
        """Offer ``item`` until it fits or the consumer signalled stop."""
        while not stop.is_set():
            try:
                q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def producer():
        try:
            for c in chunks:
                if stop.is_set():
                    return
                with span("engine.place"):
                    placed = put(c)
                if not _put(placed):
                    return
        except BaseException as e:  # noqa: BLE001 — re-raised on the consumer
            errors.append(e)
        finally:
            _put(sentinel)

    t = threading.Thread(target=producer, daemon=True,
                         name="engine-prefetch")
    t.start()
    try:
        while True:
            with span("engine.wait_chunk"):
                item = q.get()
            if item is sentinel:
                break
            yield item
        t.join()
        if errors:
            raise errors[0]
    finally:
        # Consumer gone early (exception in the training step, generator
        # closed): tell the producer to stop and unblock any pending put so
        # neither the thread nor its staged device buffers outlive this
        # iterator.
        stop.set()
        while True:
            try:
                q.get_nowait()
            except queue.Empty:
                break
        t.join(timeout=5.0)


def _as_host_dict(batch) -> dict:
    with span("engine.to_host"):
        if dataclasses.is_dataclass(batch) and not isinstance(batch, dict):
            # The batch's own arrays, not copies: the chunk stacking below
            # writes every field into its chunk buffer once.
            d = {f.name: getattr(batch, f.name)
                 for f in dataclasses.fields(batch)}
        else:
            d = dict(batch)
        # Optional batch fields (the SSLBatch tile layout when the pipeline
        # has no layout_bt) are None — drop them so chunk stacking and
        # device placement only ever see arrays.
        return {k: v for k, v in d.items() if v is not None}


def _steps(placed) -> int:
    """Steps in a placed chunk (its leading, scan axis)."""
    return int(jax.tree_util.tree_leaves(placed)[0].shape[0])


def _stack_steps(vals: list):
    """One field's per-step values stacked into an (S, ...) array.  A step
    held as a :class:`SparseBlock` is scattered into its zeroed slot, so
    its dense form is written exactly once; a dense step is copied in."""
    if not any(isinstance(v, SparseBlock) for v in vals):
        return np.stack(vals)
    shape = vals[0].shape
    if any(v.shape != shape for v in vals):
        raise ValueError(f"steps of one field differ in shape: "
                         f"{[v.shape for v in vals]}")
    out = np.zeros((len(vals),) + shape,
                   np.result_type(*(v.dtype for v in vals)))
    for slot, v in zip(out, vals):
        if isinstance(v, SparseBlock):
            v.scatter_into(slot)
        else:
            slot[...] = v
    return out


def _stack_chunk(batches: list[dict]) -> dict:
    """Stack per-step host batches into one (S, ...) scan chunk.  The
    span's stats count the chunk's (P, P) affinity blocks by how they were
    written: ``w_scattered`` from entries, ``w_copied`` from dense arrays."""
    with span("engine.stack") as sp:
        chunk = {k: _stack_steps([b[k] for b in batches])
                 for k in batches[0]}
        if "W" in batches[0]:
            blocks = [(isinstance(b["W"], SparseBlock),
                       math.prod(b["W"].shape[:-2])) for b in batches]
            sp.set_metadata(
                w_scattered=sum(n for sparse, n in blocks if sparse),
                w_copied=sum(n for sparse, n in blocks if not sparse))
        return chunk


# ---------------------------------------------------------------- strategies
class SequentialStrategy:
    """Single-device execution: the scan body is the step function itself."""

    def __init__(self, engine: "Engine"):
        self.engine = engine
        if engine.step_fn is None:
            raise ValueError(f"strategy {type(self).__name__} needs step_fn=")

    # Placement ----------------------------------------------------------
    def place_state(self, state: TrainState) -> TrainState:
        return state

    def place_batch(self, chunk: dict) -> dict:
        return jax.tree.map(jnp.asarray, chunk)

    def place_carry(self, carry):
        """Re-place a carry restored from a (host, numpy) checkpoint."""
        return jax.tree.map(jnp.asarray, carry)

    # Carry lifecycle ----------------------------------------------------
    def init_carry(self, state: TrainState):
        return state

    def begin_epoch(self, carry):
        return carry

    def state_of(self, carry) -> TrainState:
        return carry

    # Scan body ----------------------------------------------------------
    def body(self, carry, batch, lr):
        return self.engine.step_fn(carry, batch, lr)


class SyncMeshStrategy(SequentialStrategy):
    """Data-parallel k workers: params replicated over a ``("data",)``
    mesh, each chunk's leading worker axis (axis 1 — axis 0 is the scan
    axis) sharded over it.  The step maps its workers under ``shard_map``
    (``dnn_ssl_loss(mesh=...)``), since Pallas kernels cannot be
    partitioned automatically."""

    def __init__(self, engine: "Engine"):
        super().__init__(engine)
        if engine.mesh is None:
            raise ValueError("strategy 'sync_mesh' needs mesh= (a ('data',) "
                             "mesh); use repro.train.engine.data_mesh")
        P = jax.sharding.PartitionSpec
        self._replicated = jax.sharding.NamedSharding(engine.mesh, P())
        self._sharded = jax.sharding.NamedSharding(engine.mesh,
                                                   P(None, MESH_AXIS))

    def place_state(self, state: TrainState) -> TrainState:
        return jax.device_put(state, self._replicated)

    def place_batch(self, chunk: dict) -> dict:
        # Straight from host memory: each device receives only its shard
        # (staging through jnp.asarray would land the whole chunk on the
        # default device first).
        return jax.device_put(chunk, self._sharded)

    def place_carry(self, carry):
        return jax.device_put(carry, self._replicated)


class AsyncPSStrategy:
    """Stale-gradient parameter-server simulation as a scan body.

    Carry = (state, snapshots, ages, t): ``snapshots`` stacks k per-worker
    parameter copies, ``ages[w]`` counts pushes since worker w last pulled,
    ``t`` is the epoch-local step (the round-robin schedule restarts each
    epoch, matching the reference simulation).  Worker ``t % k`` computes a
    gradient at its snapshot via ``engine.grad_fn`` (which shares
    ``dnn_ssl_step``'s loss plumbing and the PAIRWISE registry); the server
    applies it to the live params immediately; the worker pulls fresh params
    once its age reaches ``max_staleness``.
    """

    def __init__(self, engine: "Engine"):
        self.engine = engine
        if engine.grad_fn is None or engine.opt is None:
            raise ValueError("strategy 'async_ps' needs grad_fn= and opt=")
        self.k = engine.n_workers
        self.max_staleness = engine.max_staleness
        self.drop_overstale = bool(
            getattr(engine.resilience, "drop_overstale", False))

    # Placement ----------------------------------------------------------
    def place_state(self, state: TrainState) -> TrainState:
        return state

    def place_batch(self, chunk: dict) -> dict:
        return jax.tree.map(jnp.asarray, chunk)

    def place_carry(self, carry):
        return jax.tree.map(jnp.asarray, carry)

    # Carry lifecycle ----------------------------------------------------
    def init_carry(self, state: TrainState):
        snapshots = jax.tree.map(lambda p: jnp.stack([p] * self.k),
                                 state.params)
        ages = jnp.zeros((self.k,), jnp.int32)
        return (state, snapshots, ages, jnp.zeros((), jnp.int32))

    def begin_epoch(self, carry):
        state, snapshots, ages, _ = carry
        return (state, snapshots, ages, jnp.zeros((), jnp.int32))

    def state_of(self, carry) -> TrainState:
        return carry[0]

    # Fault hooks --------------------------------------------------------
    def bump_age(self, carry, worker: int, amount: float):
        """Host-side injection hook: age worker ``worker % k`` by
        ``amount`` pushes (default: past ``max_staleness``, i.e. dead)."""
        state, snapshots, ages, t = carry
        amt = int(amount) or (self.max_staleness + 1)
        ages = ages.at[int(worker) % self.k].add(jnp.int32(amt))
        return (state, snapshots, ages, t)

    # Scan body ----------------------------------------------------------
    def body(self, carry, batch, lr):
        state, snapshots, ages, t = carry
        w = t % self.k
        snap_w = jax.tree.map(lambda s: s[w], snapshots)
        grads, metrics = self.engine.grad_fn(snap_w, batch)
        if self.drop_overstale:
            # A snapshot older than max_staleness is a dead/straggler
            # worker: drop its gradient (zero-gradient server update keeps
            # params and adagrad accumulators unchanged) and renormalize
            # the survivors' contribution so the effective per-pass
            # gradient mass matches the all-alive schedule.
            live = ages <= self.max_staleness
            n_live = jnp.maximum(jnp.sum(live.astype(jnp.int32)), 1)
            scale = jnp.where(live[w], self.k / n_live, 0.0).astype(
                jnp.float32)
            grads = jax.tree.map(
                lambda g: (g * scale).astype(g.dtype), grads)
            metrics = dict(metrics)
            metrics["async/dropped"] = 1.0 - jnp.where(live[w], 1.0, 0.0)
        with scope("optimizer"):
            params, opt_state = self.engine.opt.update(
                grads, state.opt_state, state.params, lr)
        ages = ages.at[w].add(1)
        refresh = ages[w] >= self.max_staleness
        snapshots = jax.tree.map(
            lambda s, p: s.at[w].set(jnp.where(refresh, p, s[w])),
            snapshots, params)
        ages = ages.at[w].set(jnp.where(refresh, 0, ages[w]))
        state = TrainState(params=params, opt_state=opt_state,
                           rng=state.rng, step=state.step + 1)
        return (state, snapshots, ages, t + 1), metrics


# -------------------------------------------------------------------- engine
class Engine:
    """Scan-compiled trainer: one jitted ``lax.scan`` per chunk of steps.

    Args:
      step_fn: ``(state, batch, lr) -> (state, metrics)`` — the per-step
        update used by ``sequential``/``sync_mesh`` (and any custom strategy
        that calls it).
      grad_fn: ``(params, batch) -> (grads, metrics)`` — gradient at given
        (possibly stale) params; required by ``async_ps``.
      opt: the ``repro.optim.Optimizer`` applying server updates
        (``async_ps`` only — synchronous strategies fold the update into
        ``step_fn``).
      strategy: STRATEGY registry name or an already-constructed instance.
      scan_chunk: steps per compiled scan; 0 compiles the whole epoch.
      prefetch: host→device prefetch depth (2 = double buffering; 0 = off).
      checkpoint_every/checkpoint_dir: save the full strategy carry every N
        epochs; ``run(..., resume=True)`` restores the newest one.
      resilience: an (optional) ``ResilienceConfig``-shaped object enabling
        the defenses — ``nonfinite_guard`` (plain scan body plus one
        per-chunk finiteness reduction folded into a ``tainted`` flag,
        resolved once per ``guard_window`` chunks; tainted windows are
        replayed from a window-start backup with the strict
        update-skipping body, which recomputes exact skipped-step
        accounting), ``halt_after_consecutive`` (host-side
        :class:`NonFiniteHaltError` policy), ``checkpoint_checksums`` /
        ``keep_last`` (integrity + retention), ``drop_overstale``
        (async_ps survivor renormalization), and the supervisor's retry /
        backoff / hang-timeout knobs for the prefetch producer.
      injector: an (optional) ``repro.resilience.FaultInjector`` whose
        batch / prefetch / checkpoint / worker hooks fire at their planned
        coordinates (chaos testing only — ``None`` in production).
      supervisor: override the prefetch supervisor (tests inject a
        no-sleep one); by default one is built from ``resilience``.
      capture_fn: ``(params, batch) -> array`` — optional per-step embedding
        tap (the online affinity refresh uses the hidden activations).  On
        epochs selected by ``run(..., capture_epochs=...)`` it is evaluated
        inside the scan body at the *post-step* params and its outputs ride
        the stacked scan metrics (ys, not the donated carry — donation-safe)
        back to the host, where ``on_epoch_end`` receives them concatenated
        over the epoch's steps.  Off-epochs compile the exact same body as
        ``capture_fn=None`` (the flag is a jit-static arg), so the hook is
        zero-cost when idle.
    """

    def __init__(
        self,
        step_fn: Callable | None = None,
        *,
        grad_fn: Callable | None = None,
        opt=None,
        strategy: str | Any = "sequential",
        mesh=None,
        n_workers: int = 1,
        max_staleness: int = 2,
        scan_chunk: int = 0,
        prefetch: int = 2,
        checkpoint_every: int = 0,
        checkpoint_dir: str | None = None,
        resilience=None,
        injector=None,
        supervisor: Supervisor | None = None,
        capture_fn: Callable | None = None,
    ):
        if scan_chunk < 0:
            raise ValueError(f"scan_chunk must be >= 0, got {scan_chunk}")
        if checkpoint_every < 0:
            raise ValueError(
                f"checkpoint_every must be >= 0, got {checkpoint_every}")
        if checkpoint_every > 0 and not checkpoint_dir:
            raise ValueError("checkpoint_every > 0 requires checkpoint_dir")
        self.step_fn = step_fn
        self.grad_fn = grad_fn
        self.opt = opt
        self.mesh = mesh
        self.n_workers = n_workers
        self.max_staleness = max_staleness
        self.scan_chunk = scan_chunk
        self.prefetch = prefetch
        self.checkpoint_every = checkpoint_every
        self.checkpoint_dir = checkpoint_dir
        # Resilience knobs are duck-typed off the config object so the
        # engine stays constructible without repro.api; all defaults
        # reproduce the pre-resilience behaviour exactly.
        self.resilience = resilience
        self.injector = injector
        self.capture_fn = capture_fn
        self._guard = bool(getattr(resilience, "nonfinite_guard", False))
        self._halt_after = int(
            getattr(resilience, "halt_after_consecutive", 0) or 0)
        self._checksums = bool(
            getattr(resilience, "checkpoint_checksums", True))
        self._keep_last = int(getattr(resilience, "keep_last", 0) or 0)
        if supervisor is None and resilience is not None:
            supervisor = Supervisor.from_config(resilience, name="prefetch")
        self.supervisor = supervisor
        if isinstance(strategy, str):
            # Lazy import: keeps repro.train importable without repro.api
            # having been set up first (no cycle either way — api.registry
            # only *names* this module).
            from repro.api.registry import STRATEGY
            strategy = STRATEGY.get(strategy)(self)
        self.strategy = strategy
        # Guarded chunks resolve in windows of this many chunks: one guard-
        # scalar fetch (and one replay backup + retained placed chunks) per
        # window instead of per chunk.
        self._guard_window = max(
            1, int(getattr(resilience, "guard_window", 4) or 4))
        # One jitted scan per chunk length (jit caches by shape).  The
        # carry is donated so state buffers are reused in place chunk to
        # chunk — except at a guard window's first chunk, whose *undonated*
        # input carry survives the call and serves as the free backup a
        # tainted window's strict replay restarts from.
        # ``capture`` is static: an off-epoch traces the identical body a
        # capture-free engine would, a capture epoch gets its own cached
        # executable with the embedding ys added.
        self._chunk_fn = jax.jit(self._run_chunk, donate_argnums=(0,),
                                 static_argnums=(3,))
        self._chunk_keep = jax.jit(self._run_chunk, static_argnums=(3,))
        # The strict guard body only compiles if a window ever needs the
        # replay (lazily, on first call) — clean runs never pay for it.
        self._strict_fn = jax.jit(self._run_chunk_strict, static_argnums=(3,))

    # ---------------------------------------------------------------- scan
    #: Metrics key the capture tap rides under; popped out of the metric
    #: chunks (and concatenated for ``on_epoch_end``) before row averaging.
    _CAPTURE_KEY = "capture/emb"

    def _step_body(self, lr, capture: bool):
        """The scan body, optionally extended with the embedding tap."""
        def body(c, b):
            c2, m = self.strategy.body(c, b, lr)
            if capture:
                m = dict(m)
                m[self._CAPTURE_KEY] = self.capture_fn(
                    self.strategy.state_of(c2).params, b)
            return c2, m

        return body

    @scope("chunk")
    def _run_chunk(self, carry, batches, lr, capture: bool = False):
        """The hot path.  With the guard on the scan body is *identical* to
        the unguarded one — no per-step check, count, or select.  The only
        additions are a single post-scan finiteness reduction over the
        chunk's final carry and stacked per-step metrics, folded into a
        ``tainted`` flag threaded through the carry, and a ``guard/skipped``
        zeros column so metric rows keep one schema.  The run loop fetches
        the guard scalars once per *window* of chunks; a tainted window is
        discarded and replayed from its start with
        :meth:`_run_chunk_strict`, which recomputes the exact skip
        accounting.  Clean windows — the overwhelming case — pay one
        finiteness reduction per chunk and one scalar fetch per window."""
        body = self._step_body(lr, capture)

        if not self._guard:
            return jax.lax.scan(body, carry, batches)

        sc, (skipped, consec, worst, tainted) = carry
        out_sc, metrics = jax.lax.scan(body, sc, batches)
        # Stacked metrics give per-step visibility, so even a transient
        # non-finite that the carry later masks still taints the window.
        ok = all_finite((out_sc, metrics))
        n_steps = jax.tree_util.tree_leaves(batches)[0].shape[0]
        metrics = dict(metrics)
        metrics["guard/skipped"] = jnp.zeros((n_steps,), jnp.float32)
        # A clean chunk proves every step was fine, so the consecutive
        # counter resets; on taint its value is garbage anyway — the strict
        # replay restarts from the window backup's (correct) guard state.
        guard = (skipped, jnp.where(ok, jnp.int32(0), consec), worst,
                 jnp.logical_or(tainted, ~ok))
        return (out_sc, guard), metrics

    def _run_chunk_strict(self, carry, batches, lr, capture: bool = False):
        """The replay path for a window the hot pass tainted: the per-step
        guarded body with exact skip accounting."""
        body = self._step_body(lr, capture)

        def guarded(c, b):
            sc, (skipped, consec, worst) = c
            new_sc, metrics = body(sc, b)
            ok = all_finite((new_sc, metrics))
            # Skip the whole update on a non-finite step: params, opt
            # state, rng, step counter — the carry is exactly what it was,
            # as if the poisoned batch had never been drawn.
            keep = jax.lax.cond(ok, lambda: new_sc, lambda: sc)
            bad = (~ok).astype(jnp.int32)
            consec = jnp.where(ok, jnp.int32(0), consec + 1)
            # Zero the skipped step's metrics so epoch means stay finite.
            metrics = jax.tree.map(
                lambda m: jnp.where(ok, m, jnp.zeros_like(m)), metrics)
            metrics = dict(metrics)
            metrics["guard/skipped"] = bad.astype(jnp.float32)
            guard = (skipped + bad, consec, jnp.maximum(worst, consec))
            return (keep, guard), metrics

        sc, (skipped, consec, worst, _) = carry
        (out_sc, counters), metrics = jax.lax.scan(
            guarded, (sc, (skipped, consec, worst)), batches)
        return (out_sc, (*counters, jnp.zeros((), jnp.bool_))), metrics

    # Guard carry plumbing: with the guard on, the jitted carry is
    # ``(strategy_carry, (skipped_total, consecutive, worst, tainted))`` —
    # these helpers keep strategy lifecycle hooks working on their own
    # carry.
    def _wrap_carry(self, strategy_carry, guard_state=None):
        if not self._guard:
            return strategy_carry
        return (strategy_carry, guard_state or guard_init())

    def _split_carry(self, carry):
        if not self._guard:
            return carry, None
        return carry

    def _bump(self, strategy, carry, bump):
        """Apply a recorded worker-age bump to the (wrapped) carry — used
        both on first dispatch and when a strict replay re-dispatches the
        chunks younger than a poisoned one."""
        if bump is None:
            return carry
        sc, gs = self._split_carry(carry)
        return self._wrap_carry(strategy.bump_age(sc, bump[0], bump[1]), gs)

    def _host_chunks(self, batch_iter: Iterable, epoch: int = 0
                     ) -> Iterator[dict]:
        """Group host batches into stacked (S, ...) scan chunks (poisoning
        any step with an armed batch-site fault event)."""
        pending: list[dict] = []
        step = 0
        for b in batch_iter:
            h = _as_host_dict(b)
            if self.injector is not None:
                h = self.injector.on_batch(h, epoch=epoch, step=step)
            step += 1
            pending.append(h)
            if self.scan_chunk and len(pending) == self.scan_chunk:
                yield _stack_chunk(pending)
                pending = []
        if pending:
            yield _stack_chunk(pending)

    # ---------------------------------------------------------- checkpoints
    def _ckpt_path(self, epoch: int) -> str:
        return os.path.join(self.checkpoint_dir, f"ckpt_{epoch:05d}")

    def _save(self, carry, epoch: int, history: list[dict]) -> None:
        path = self._ckpt_path(epoch)
        save_checkpoint(path, carry, checksum=self._checksums)
        atomic_write_text(path + ".meta.json",
                          json.dumps({"epoch": epoch, "history": history}))
        atomic_write_text(os.path.join(self.checkpoint_dir, _LATEST),
                          os.path.basename(path))
        if self.injector is not None:
            # Simulated bit rot / torn write of the file LATEST points at —
            # AFTER the pointer update, so recovery must fall back.
            self.injector.after_checkpoint(path + ".npz", epoch=epoch)
        if self._keep_last:
            self._prune(keep=os.path.basename(path))

    def _prune(self, keep: str) -> None:
        """Drop all but the newest ``keep_last`` checkpoints (never the one
        just written).  Epoch numbers order lexically at fixed width."""
        names = sorted(
            (f[:-len(".npz")] for f in os.listdir(self.checkpoint_dir)
             if f.startswith("ckpt_") and f.endswith(".npz")), reverse=True)
        for base in names[self._keep_last:]:
            if base == keep:
                continue
            stem = os.path.join(self.checkpoint_dir, base)
            for suffix in (".npz", ".npz.sha256", ".meta.json"):
                if os.path.exists(stem + suffix):
                    os.remove(stem + suffix)

    def _load_latest(self, template_carry):
        """(carry, completed_epochs, history) from the newest *valid*
        checkpoint, or None when the directory holds none.

        The LATEST pointer's target is tried first; if it is corrupt
        (checksum mismatch, torn archive, unreadable meta) the remaining
        ``ckpt_*`` files are tried newest-first, each failure downgraded
        to a warning — a crash or bit flip costs at most the epochs since
        the last good save, never the run.
        """
        if not self.checkpoint_dir or not os.path.isdir(self.checkpoint_dir):
            return None
        pointer = os.path.join(self.checkpoint_dir, _LATEST)
        candidates: list[str] = []
        if os.path.exists(pointer):
            with open(pointer) as f:
                candidates.append(f.read().strip())
        candidates += sorted(
            (f[:-len(".npz")] for f in os.listdir(self.checkpoint_dir)
             if f.startswith("ckpt_") and f.endswith(".npz")), reverse=True)
        seen: set[str] = set()
        for base in candidates:
            if not base or base in seen:
                continue
            seen.add(base)
            path = os.path.join(self.checkpoint_dir, base)
            try:
                carry = load_checkpoint(path, template_carry,
                                        verify=self._checksums)
                with open(path + ".meta.json") as f:
                    meta = json.load(f)
                epoch, hist = int(meta["epoch"]), list(meta["history"])
            except Exception as e:  # noqa: BLE001 — degrade to older ckpt
                warnings.warn(
                    f"checkpoint {base} is unusable "
                    f"({type(e).__name__}: {e}); falling back to the next "
                    "newest", stacklevel=2)
                continue
            return (self.strategy.place_carry(carry), epoch, hist)
        return None

    # ----------------------------------------------------------------- run
    def run(
        self,
        pipeline_epoch: Callable[[], Iterable],
        *,
        state: TrainState,
        n_epochs: int,
        lr_schedule: Callable[[int], float],
        eval_fn: Callable[[Any], dict] | None = None,
        resume: bool = False,
        capture_epochs: Callable[[int], bool] | Any = None,
        on_epoch_end: Callable[[int, Any, Any], None] | None = None,
    ) -> EngineResult:
        """Train for ``n_epochs`` passes of ``pipeline_epoch()`` batches.

        ``pipeline_epoch`` is called once per epoch and must yield host
        batches (dicts or dataclasses of equal-shaped numpy arrays).
        Accepting an ``epoch=`` keyword declares the pipeline *epoch-pure*:
        the true epoch index is passed, resume skips the host-side replay
        of earlier epochs entirely (an epoch-pure pipeline reproduces any
        epoch from its index alone — the re-partitioning stream does), and
        an ``n_epochs=`` keyword additionally receives the horizon (so the
        stream can skip pre-computing plans no epoch will consume).
        ``eval_fn(params) -> dict`` is merged into each epoch row.  With
        ``resume=True`` and a checkpoint present in ``checkpoint_dir``,
        training restarts from the saved carry/epoch; for epoch-blind
        pipelines the skipped epochs' batch iterators are drained so
        host-side pipeline RNG replays the exact stream an uninterrupted
        run would have seen.

        ``capture_epochs`` (a predicate ``epoch -> bool``, or a container
        of epoch indices) selects the epochs whose steps evaluate the
        engine's ``capture_fn``; ``on_epoch_end(epoch, params, captures)``
        then fires after every epoch row with the epoch's captures stacked
        ``(steps, ...)`` on the host (``None`` on non-capture epochs) —
        the online refresh hook.  On a guard-replayed window, skipped
        steps' captures are zeroed like their metrics.
        """
        strategy = self.strategy
        # Epoch purity is a semantic contract — only an explicitly named
        # ``epoch`` parameter opts in (a **kwargs catch-all does not).
        takes_epoch = accepts_kwarg(pipeline_epoch, "epoch", explicit=True)
        extra = ({"n_epochs": n_epochs}
                 if takes_epoch and accepts_kwarg(pipeline_epoch, "n_epochs",
                                                  explicit=True)
                 else {})

        def epoch_batches(e: int):
            return pipeline_epoch(epoch=e, **extra) if takes_epoch \
                else pipeline_epoch()

        start, history = 0, []
        # Copy the initial leaves: the first chunk call DONATES the carry,
        # and caller-owned buffers (e.g. a params pytree reused across runs)
        # must survive this run.
        state = jax.tree.map(lambda x: jnp.array(x), state)
        carry = self._wrap_carry(strategy.init_carry(
            strategy.place_state(state)))
        if resume:
            loaded = self._load_latest(carry)
            if loaded is not None:
                carry, start, history = loaded
        if start < n_epochs and not takes_epoch:
            # Epoch-blind pipelines advance host RNG per call: replay the
            # skipped epochs (data pass only, no compute).  Epoch-pure
            # pipelines reproduce epoch ``start`` from its index directly.
            for past in range(start):
                for _ in epoch_batches(past):
                    pass
        def capture_on(e: int) -> bool:
            if self.capture_fn is None or capture_epochs is None:
                return False
            if callable(capture_epochs):
                return bool(capture_epochs(e))
            return e in capture_epochs

        for epoch in range(start, n_epochs):
            lr = jnp.float32(lr_schedule(epoch))
            cap = capture_on(epoch)
            t0 = time.perf_counter()
            sc, gs = self._split_carry(carry)
            carry = self._wrap_carry(strategy.begin_epoch(sc), gs)
            metric_chunks = []
            put = strategy.place_batch
            if self.injector is not None:
                put = self.injector.wrap_put(put, epoch=epoch)
            if self.supervisor is not None:
                put = functools.partial(self.supervisor.call, put,
                                        key=f"prefetch@{epoch}")
            chunks = prefetch_to_device(
                self._host_chunks(epoch_batches(epoch), epoch),
                put, self.prefetch)
            # Guarded chunks are grouped into windows of ``guard_window``
            # chunks.  Each window keeps its start carry (undonated — the
            # replay backup) and its placed chunks; one guard-scalar fetch
            # per window, resolved one chunk behind the dispatch so the
            # fetch overlaps the successor's compute.  Each window item is
            # ``[chunk_idx, placed, metrics]``.
            win: list = []                  # the window currently filling
            win_backup = None               # carry before win[0]
            done: deque = deque()           # (backup, items, carry_out)
            bumps: dict[int, tuple] = {}    # chunk_idx -> (worker, amount)

            def dispatch(item):
                nonlocal carry, win_backup
                first = not win
                if first:
                    win_backup = carry
                carry = self._bump(strategy, carry, bumps.get(item[0]))
                # The window's first chunk must not donate its input: the
                # backup has to survive for a possible strict replay.
                with span("engine.dispatch", steps=_steps(item[1])):
                    carry, item[2] = (self._chunk_keep if first else
                                      self._chunk_fn)(carry, item[1], lr, cap)
                win.append(item)
                if len(win) == self._guard_window:
                    done.append((win_backup, win[:], carry))
                    win.clear()

            def resolve_window():
                nonlocal carry
                backup, items, out = done.popleft()
                gs = self._split_carry(out)[1]
                with span("engine.guard_fetch"):
                    skipped, worst, tainted = (
                        v.item() for v in
                        jax.device_get((gs[0], gs[2], gs[3])))
                if tainted:
                    # Non-finite step(s) somewhere in this window: discard
                    # the hot pass and replay the window strictly from its
                    # backup, skipping exactly the poisoned steps; then
                    # re-dispatch everything younger, which consumed the
                    # poisoned carry.
                    cur = backup
                    for item in items:
                        cur = self._bump(strategy, cur, bumps.get(item[0]))
                        with span("engine.dispatch", steps=_steps(item[1])):
                            cur, item[2] = self._strict_fn(cur, item[1], lr,
                                                           cap)
                    gs = self._split_carry(cur)[1]
                    skipped, worst = (int(v) for v in
                                      jax.device_get((gs[0], gs[2])))
                    younger = [it for _, its, _ in done for it in its]
                    younger += win
                    done.clear()
                    win.clear()
                    carry = cur
                    for item in younger:
                        dispatch(item)
                metric_chunks.extend(item[2] for item in items)
                if self._halt_after and worst >= self._halt_after:
                    # Exact at window edges (the strict replay above just
                    # recomputed it when this window held the poison).
                    raise NonFiniteHaltError(
                        f"{worst} consecutive non-finite steps "
                        f"(halt_after_consecutive={self._halt_after}) "
                        f"at epoch {epoch}")

            for chunk_idx, placed in enumerate(chunks):
                if self.injector is not None and \
                        hasattr(strategy, "bump_age"):
                    ev = self.injector.take("worker", epoch=epoch,
                                            step=chunk_idx)
                    if ev is not None:
                        # Recorded so a tainted window's re-dispatch of
                        # this chunk re-applies the same age bump.
                        bumps[chunk_idx] = (ev.worker, ev.arg)
                if not self._guard:
                    carry = self._bump(strategy, carry, bumps.get(chunk_idx))
                    with span("engine.dispatch", steps=_steps(placed)):
                        carry, metrics = self._chunk_fn(carry, placed, lr, cap)
                    metric_chunks.append(metrics)   # fetched after the epoch
                    continue
                dispatch([chunk_idx, placed, None])
                if done and (win or len(done) > 1):
                    resolve_window()
            while done or win:
                if win and not done:        # roll the final partial window
                    done.append((win_backup, win[:], carry))
                    win.clear()
                resolve_window()
            with span("engine.epoch_end"):
                if not metric_chunks:
                    # e.g. n_meta < n_workers: the pipeline had nothing to
                    # yield.
                    warnings.warn(
                        f"epoch {epoch}: pipeline yielded no batches "
                        "(n_meta < n_workers?); skipping epoch row",
                        stacklevel=2)
                    continue
                with span("engine.metrics_fetch"):
                    captures = None
                    if cap:
                        # Pull the tap out of the metric chunks (it must not
                        # enter the row means) and stack it (total_steps, ...)
                        # on host.
                        captures = np.concatenate(
                            [np.asarray(jax.device_get(
                                mc.pop(self._CAPTURE_KEY)))
                             for mc in metric_chunks])
                    row = {
                        k: float(np.mean(np.concatenate(
                            [np.asarray(mc[k]) for mc in metric_chunks])))
                        for k in metric_chunks[0]
                    }
                    row.update(epoch=epoch, lr=float(lr),
                               seconds=time.perf_counter() - t0)
                    if self._guard:
                        row["guard/skipped_total"] = int(
                            jax.device_get(self._split_carry(carry)[1][0]))
                if eval_fn is not None:
                    with span("engine.eval"):
                        row.update(eval_fn(strategy.state_of(
                            self._split_carry(carry)[0]).params))
                history.append(row)
                if on_epoch_end is not None:
                    with span("engine.on_epoch_end"):
                        on_epoch_end(epoch, strategy.state_of(
                            self._split_carry(carry)[0]).params, captures)
                if self.checkpoint_every and \
                        (epoch + 1) % self.checkpoint_every == 0:
                    with span("engine.checkpoint"):
                        self._save(carry, epoch + 1, history)
        return EngineResult(
            state=strategy.state_of(self._split_carry(carry)[0]),
            history=history)
