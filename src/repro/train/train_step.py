"""Train steps: the paper's SSL DNN step and the LM steps for assigned archs.

``dnn_ssl_step``   — the paper's objective (Eq. 3) on the 4×2000 DNN, over a
                     (k, P, ·) stack of concatenated meta-batches.  Under the
                     ``sync_mesh`` strategy the leading axis is sharded over
                     the data mesh, which *is* the paper's k-worker
                     synchronous SGD: each device maps its workers under
                     ``shard_map`` and the gradient all-reduce does what the
                     parameter server did.
``lm_train_step``  — next-token loss for any assigned architecture, with the
                     paper's graph regularizer attached at the sequence level
                     (pooled output distribution + dense affinity block W).
``lm_supervised_step`` — same without the SSL terms (the paper's
                     fully-supervised baseline, and the dry-run default).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.ssl_loss import SSLHyper, ssl_objective
from repro.models import transformer as tf
from repro.models.config import ModelConfig
from repro.models.dnn import DNNConfig, dnn_forward
from repro.optim import Optimizer
from repro.tracing import scope

Array = jax.Array


# ------------------------------------------------------------------ DNN/SSL
#: SSLBatch block-layout fields, in ``BlockLayout.arrays()`` order — the
#: tuple the layout-aware pairwise kernels consume.
_TILE_KEYS = ("tile_rows", "tile_cols", "tile_valid",
              "tile_crows", "tile_ccols", "tile_cvalid", "tile_occ")


def dnn_ssl_loss(params, batch: dict, cfg: DNNConfig, hyper: SSLHyper,
                 *, dropout_rng=None, dropout: float = 0.0, pairwise=None,
                 mesh=None):
    """Mean Eq.-3 loss over the k stacked concatenated batches.

    ``pairwise`` names a PAIRWISE registry entry ("ref" | "pallas" |
    "fused" | "blocksparse" | "auto") or is an already-resolved
    ``(logp, W) -> scalar`` callable; ``None`` keeps the inline jnp oracle.
    When the pipeline attached a block layout (the ``tile_*`` batch keys,
    from ``BatchConfig.layout_bt``) it rides through the vmap and into
    layout-aware kernels, which skip W's structurally-zero tiles.

    ``mesh`` (the ``sync_mesh`` strategy's data mesh) maps the workers
    under ``shard_map``: each device runs the per-worker loss of its own
    shard of k, because the compiler cannot partition a Pallas kernel by
    itself.  The mean over workers, and hence the gradient all-reduce
    (the transpose of the replicated ``params`` input), stays outside.
    """
    tile_args = ([batch[k] for k in _TILE_KEYS]
                 if all(batch.get(k) is not None for k in _TILE_KEYS)
                 else [])

    def per_worker(params, dropout_rng, x, y, mask, W, valid, *tiles):
        with scope("dnn"):
            logits = dnn_forward(params, x, dropout_rng=dropout_rng,
                                 dropout=dropout)
        # Padding rows: zero affinity + zero label mask + masked entropy term.
        mask = mask * valid
        Wm = W * valid[:, None] * valid[None, :]
        loss, metrics = ssl_objective(
            logits, y, mask, Wm, hyper, params=params, pairwise=pairwise,
            layout=tuple(tiles) or None, reduction="mean")
        return loss, metrics

    per_k = (batch["x"], batch["y"], batch["label_mask"], batch["W"],
             batch["valid"].astype(jnp.float32), *tile_args)
    workers = jax.vmap(per_worker, in_axes=(None, None) + (0,) * len(per_k))
    if mesh is not None:
        P = jax.sharding.PartitionSpec
        # check_vma=False: Pallas outputs carry no varying-axes type.
        workers = jax.shard_map(
            workers, mesh=mesh,
            in_specs=(P(), P()) + (P(mesh.axis_names),) * len(per_k),
            out_specs=P(mesh.axis_names), check_vma=False)
    losses, metrics = workers(params, dropout_rng, *per_k)
    return jnp.mean(losses), jax.tree.map(jnp.mean, metrics)


def dnn_ssl_grads(params, batch: dict, *, cfg: DNNConfig, hyper: SSLHyper,
                  dropout_rng=None, dropout: float = 0.0, pairwise=None,
                  mesh=None):
    """``(grads, metrics)`` of the Eq.-3 loss at ``params``.

    The shared gradient core: ``dnn_ssl_step`` applies it synchronously;
    the engine's ``async_ps`` strategy evaluates it at a *stale* parameter
    snapshot and hands the gradient to the server update — both through the
    same loss plumbing and PAIRWISE registry selection.
    """
    (loss, metrics), grads = jax.value_and_grad(
        dnn_ssl_loss, has_aux=True)(params, batch, cfg, hyper,
                                    dropout_rng=dropout_rng, dropout=dropout,
                                    pairwise=pairwise, mesh=mesh)
    metrics["loss/total"] = loss
    return grads, metrics


def dnn_ssl_step(params, opt_state, batch: dict, *, cfg: DNNConfig,
                 hyper: SSLHyper, opt: Optimizer, lr: Array,
                 dropout_rng=None, dropout: float = 0.0, pairwise=None,
                 mesh=None):
    grads, metrics = dnn_ssl_grads(params, batch, cfg=cfg, hyper=hyper,
                                   dropout_rng=dropout_rng, dropout=dropout,
                                   pairwise=pairwise, mesh=mesh)
    with scope("optimizer"):
        new_params, new_state = opt.update(grads, opt_state, params, lr)
    return new_params, new_state, metrics


# ------------------------------------------------------------------- LM
def chunked_ce(x: Array, head: Array, targets: Array, mask: Array,
               *, chunk: int = 512) -> Array:
    """Cross-entropy over (B, T) without a live (B, T, V) logits tensor.

    Scans T in chunks of ``chunk``; each chunk's logits are recomputed in the
    backward pass (jax.checkpoint), so peak memory is O(B·chunk·V) — the
    difference between 80 GB and <1 GB per device at vocab≈150k.
    """
    B, T, d = x.shape
    c = min(chunk, T)
    pad = (-T) % c
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    nt = (T + pad) // c

    def body(carry, inp):
        xc, tc, mc = inp                       # (B, c, d), (B, c), (B, c)
        logits = jnp.einsum("bcd,dv->bcv", xc, head).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, tc[..., None].astype(jnp.int32),
                                     axis=-1)[..., 0]
        return (carry[0] - jnp.sum(picked * mc), carry[1] + jnp.sum(mc)), None

    xs = (x.reshape(B, nt, c, d).swapaxes(0, 1),
          targets.reshape(B, nt, c).swapaxes(0, 1),
          mask.reshape(B, nt, c).swapaxes(0, 1))
    (tot, cnt), _ = jax.lax.scan(jax.checkpoint(body),
                                 (jnp.float32(0), jnp.float32(0)), xs)
    return tot / jnp.maximum(cnt, 1.0)


def lm_loss(params, cfg: ModelConfig, batch: dict, hyper: SSLHyper | None,
            *, pairwise=None, act_sharding=None):
    """Next-token CE (+ optional sequence-level SSL graph regularizer)."""
    out = tf.forward(params, cfg, batch["tokens"],
                     modality_embeds=batch.get("modality_embeds"),
                     act_sharding=act_sharding, with_logits=False)
    mask = batch.get("loss_mask")
    if mask is None:
        mask = jnp.ones(batch["targets"].shape, jnp.float32)
    ce = chunked_ce(out["hidden"], tf.output_head(params, cfg),
                    batch["targets"], mask)
    loss = ce + 0.01 * out["moe_aux"]
    metrics = {"loss/ce": ce, "loss/moe_aux": out["moe_aux"]}
    if hyper is not None and "W" in batch:
        # Sequence-level graph regularizer over G independent concatenated
        # meta-batches (paper §2.3: the loss decomposes over groups; the
        # leading G axis is what the launcher shards over data — no
        # cross-worker SSL collective, exactly the paper's decomposition).
        G, b, _ = batch["W"].shape
        pooled = out["pooled_logits"].astype(jnp.float32).reshape(
            G, b, -1)

        def per_group(pl, y, m, W):
            return ssl_objective(pl, y, m, W, hyper, params=None,
                                 pairwise=pairwise, reduction="mean")

        ssl_losses, ssl_metrics = jax.vmap(per_group)(
            pooled, batch["seq_labels"], batch["seq_label_mask"], batch["W"])
        loss = loss + jnp.mean(ssl_losses)
        metrics.update({f"ssl/{k.split('/')[-1]}": jnp.mean(v)
                        for k, v in ssl_metrics.items()})
    metrics["loss/total"] = loss
    return loss, metrics


def lm_train_step(params, opt_state, batch: dict, *, cfg: ModelConfig,
                  hyper: SSLHyper | None, opt: Optimizer, lr,
                  pairwise=None, act_sharding=None):
    (loss, metrics), grads = jax.value_and_grad(lm_loss, has_aux=True)(
        params, cfg, batch, hyper, pairwise=pairwise,
        act_sharding=act_sharding)
    new_params, new_state = opt.update(grads, opt_state, params, lr)
    return new_params, new_state, metrics


def lm_supervised_step(params, opt_state, batch: dict, *, cfg: ModelConfig,
                       opt: Optimizer, lr):
    return lm_train_step(params, opt_state, batch, cfg=cfg, hyper=None,
                         opt=opt, lr=lr)
