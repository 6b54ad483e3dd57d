"""Host spans and device names the program writes into a profiler trace.

Tracing is on exactly while a ``jax.profiler`` session is open
(``jax.profiler.start_trace`` / ``jax.profiler.trace``).  Each ``span``
below is then a host event named ``repro.<name>`` on the line of the
thread that ran it, on the trace's one clock with the device's ops.
Without a session a span costs under a microsecond.  A span never stays
open across a ``yield``: a generator closes its spans before it yields,
so the consumer's time is never booked to the producer.

Host spans (``SPANS``), by where they run:

Experiment build (``Experiment.build``, the caller's thread):

* ``build.corpus``: the synthetic corpus and its held-out split.
* ``build.graph``: the k-NN affinity graph (on the device with the
  ``pallas`` construction: the ``knn_topk`` kernel).
* ``build.plan``: the initial partition and meta-batch plan.
* ``build.pipeline``: the batch pipeline (and its replan cache).

Batch pipeline (``data/pipeline.py``; under the engine, the prefetch
producer thread):

* ``pipeline.epoch_begin``: an epoch's prologue up to its first block:
  collecting the background replan, a synchronous synthesis where one is
  due, launching the next replan, the epoch's sampler and order.
* ``replan.join``: the feed blocked on the background replan (its thread's
  join and the swap); stat ``outcome``: ``swapped``, ``kept`` (the plan
  did not fit the pinned pad) or ``failed``.
* ``replan.synthesize``: one plan synthesis, on the replan thread (or
  inside ``pipeline.epoch_begin`` when it runs synchronously).
* ``pipeline.block``: one worker's concatenated block, padded; its child
  ``pipeline.densify`` is the work on W: extracting the padded block's
  nonzero entries from the graph's CSR (W is densified later, once, by
  ``engine.stack``).
* ``pipeline.stack``: stacking a step's k blocks (W's entries joined).

Engine (``train/engine.py``):

* ``engine.to_host``: a step's batch read into a host dict, its arrays
  shared, not copied (producer).
* ``engine.stack``: a chunk's steps stacked into one (S, ...) array per
  field: W's entries scattered into the zeroed chunk buffer, a dense W
  and every other field copied in (producer); stats ``w_scattered`` and
  ``w_copied``, the chunk's (P, P) affinity blocks written each way.
* ``engine.place``: a chunk put on the device, with any supervisor or
  fault-injector wrapper (producer; in the consumer when prefetch is 0).
* ``engine.wait_chunk``: the training loop waiting for its next placed
  chunk, or for the end of the epoch's stream: one per chunk plus one per
  epoch.  With prefetch 0 it covers the synchronous production.
* ``engine.dispatch``: enqueueing one chunk program (stat ``steps``); a
  compile or a load from the compile cache shows as a long dispatch.
* ``engine.guard_fetch``: the non-finite guard's scalars fetched to the
  host, once per guard window.
* ``engine.epoch_end``: the epoch boundary, with children
  ``engine.metrics_fetch`` (the epoch's metrics to the host),
  ``engine.eval``, ``engine.on_epoch_end`` (the online-refresh hook) and
  ``engine.checkpoint``.  No chunk of the next epoch is assembled meanwhile.

Device names.  ``jax.named_scope`` regions put their name into the scope
path (HLO ``op_name``) of every op traced inside them, wrapped as
``jvp(...)`` / ``transpose(...)`` in the backward pass (``SCOPES``):
``repro.chunk`` (``Engine._run_chunk``), ``repro.dnn`` (the DNN forward),
``repro.graph_reg`` (the graph regularizer) and ``repro.optimizer`` (the
parameter update).  Each Pallas kernel has a fixed ``name=``, which
becomes its HLO instruction name: ``graph_reg_fused_reg_forward``,
``graph_reg_cross``, ``graph_reg_bwd_dlogp``, ``graph_reg_bwd_dw`` (dense
regularizer), ``graph_reg_bsp_forward``, ``graph_reg_bsp_bwd_bterm``,
``graph_reg_bsp_bwd_dlogp``, ``graph_reg_bsp_bwd_dw`` (block-sparse),
``knn_topk`` and ``rbf_affinity`` (graph build).  In a TPU trace the
instruction name starts the op's event name on the ``XLA Ops`` line
(``%graph_reg_bwd_dlogp.10 = ...``), and the scope path is the ``tf_op``
stat of the op's event metadata.
"""
from __future__ import annotations

import jax

__all__ = ["SPANS", "SCOPES", "span", "scope"]

SPANS = (
    "build.corpus", "build.graph", "build.plan", "build.pipeline",
    "pipeline.epoch_begin", "replan.join", "replan.synthesize",
    "pipeline.block", "pipeline.densify", "pipeline.stack",
    "engine.to_host", "engine.stack", "engine.place", "engine.wait_chunk",
    "engine.dispatch", "engine.guard_fetch", "engine.epoch_end",
    "engine.metrics_fetch", "engine.eval", "engine.on_epoch_end",
    "engine.checkpoint",
)

SCOPES = ("repro.chunk", "repro.dnn", "repro.graph_reg", "repro.optimizer")


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """The host span ``repro.<name>``; ``args`` become the event's stats."""
    return jax.profiler.TraceAnnotation("repro." + name, **args)


def scope(name: str):
    """The device scope ``repro.<name>`` (trace-time metadata only)."""
    return jax.named_scope("repro." + name)
