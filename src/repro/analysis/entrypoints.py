"""The audited entry points: every contract the jaxpr pass proves.

Each :class:`~repro.analysis.jaxpr_audit.EntryPoint` here names one
compiled surface of the repo together with its committed expectations —
the fused Eq.-3/4 forward+backward at **0** dense B×B intermediates, the
jnp reference kept as a canary that must still trip the counter, the
streaming k-NN at zero (N, M) materialization, and one scan-compiled
engine chunk per execution strategy with a fully-donated carry and no
host callbacks in the scan body.

Entries are exposed through the ``repro.api.registry.AUDIT`` registry so
the CLI (and any test) can audit them by name; builders construct tiny
but structurally faithful instances (real kernels, real engine, real
strategies — just small shapes), and nothing here runs device code: the
auditor only traces.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.analysis.jaxpr_audit import EntryPoint
from repro.train.engine import MESH_AXIS

__all__ = [
    "ENTRY_POINTS",
    "graph_reg_fused",
    "graph_reg_blocksparse",
    "graph_reg_ref",
    "knn_topk",
    "online_refresh",
    "ssl_objective",
    "engine_sequential",
    "engine_sync_mesh",
    "engine_async_ps",
    "engine_capture",
    "serve_decode_generate",
]

_B, _C = 256, 39                      # regularizer block: paper's 39 phones
_GAMMA, _KAPPA = 1e-3, 1e-4


def _logp_W(b: int = _B, c: int = _C):
    logp = jax.nn.log_softmax(jnp.zeros((b, c), jnp.float32), axis=-1)
    W = jnp.ones((b, b), jnp.float32)
    return logp, W


def _build_fused():
    from repro.kernels.ops import graph_regularizer_fused

    def loss_and_grads(logp, W):
        return jax.value_and_grad(
            lambda lp, w: graph_regularizer_fused(lp, w, _GAMMA, _KAPPA),
            argnums=(0, 1))(logp, W)

    return loss_and_grads, _logp_W()


def _build_blocksparse():
    """Block-sparse fwd+bwd on a block-diagonal mask (2 of 4 tiles active).

    The contract matches the dense fused path: 0 dense B×B intermediates
    outside Pallas kernels in either direction — the bwd's (B, C)-shaped
    bterm staging array is the only inter-kernel buffer, and C ≪ B here.
    """
    import numpy as np

    from repro.core.metabatch import block_layout
    from repro.kernels.ops import graph_regularizer_blocksparse

    bt = _B // 2
    Wn = np.zeros((_B, _B), np.float32)
    Wn[:bt, :bt] = 1.0
    Wn[bt:, bt:] = 1.0
    layout = tuple(jnp.asarray(a) for a in block_layout(Wn, bt).arrays())
    logp, _ = _logp_W()
    W = jnp.asarray(Wn)

    def loss_and_grads(logp, W):
        return jax.value_and_grad(
            lambda lp, w: graph_regularizer_blocksparse(
                lp, w, _GAMMA, _KAPPA, layout=layout),
            argnums=(0, 1))(logp, W)

    return loss_and_grads, (logp, W)


def _build_ref():
    from repro.kernels.ref import graph_regularizer_ref

    def loss_and_grads(logp, W):
        return jax.value_and_grad(
            lambda lp, w: graph_regularizer_ref(lp, w, _GAMMA, _KAPPA),
            argnums=(0, 1))(logp, W)

    return loss_and_grads, _logp_W()


def _build_knn():
    from repro.kernels.ops import knn_topk as knn

    n, d, k = _B, 64, 8
    x = jnp.zeros((n, d), jnp.float32)

    def run(x):
        return knn(x, x, k, exclude_self=True, use_pallas=True)

    return run, (x,)


def _build_online_refresh():
    """Embedding-space top-k of the online graph refresh (``repro.online``).

    Same contract as construction-time ``knn_topk``: the refresh must
    never materialize the dense N×N embedding-distance matrix — the
    running top-k lives in the Pallas kernel's VMEM scratch.
    """
    from repro.online import embedding_topk_device

    n, d, k = _B, 64, 8
    e = jnp.zeros((n, d), jnp.float32)

    def run(e):
        return embedding_topk_device(e, k)

    return run, (e,)


def _build_ssl_objective():
    from repro.core.ssl_loss import SSLHyper, ssl_objective as objective

    logp, W = _logp_W()
    labels = jnp.zeros((_B,), jnp.int32)
    mask = jnp.ones((_B,), jnp.float32)
    hyper = SSLHyper(gamma=_GAMMA, kappa=_KAPPA)

    def loss_and_grads(logits, labels, mask, W):
        return jax.value_and_grad(
            lambda lg: objective(lg, labels, mask, W, hyper,
                                 pairwise="fused")[0])(logits)

    return loss_and_grads, (logp, labels, mask, W)


# ------------------------------------------------------------------ engine
def _tiny_problem():
    """Structurally faithful mini instance of the paper's DNN/SSL setup."""
    from repro.core.ssl_loss import SSLHyper
    from repro.models.dnn import DNNConfig, init_dnn
    from repro.optim import sgd

    cfg = DNNConfig(input_dim=16, hidden_dim=32, n_hidden=2, n_classes=5,
                    dropout=0.0)
    params = init_dnn(cfg, jax.random.PRNGKey(0))
    return cfg, params, SSLHyper(gamma=_GAMMA, kappa=_KAPPA), sgd()


def _tiny_batches(s: int = 2, k: int = 1, p: int = 64, d: int = 16):
    """One stacked (S, k, P, ...) scan chunk of synthesized meta-batches."""
    return {
        "x": jnp.zeros((s, k, p, d), jnp.float32),
        "y": jnp.zeros((s, k, p), jnp.int32),
        "label_mask": jnp.ones((s, k, p), jnp.float32),
        "W": jnp.ones((s, k, p, p), jnp.float32),
        "valid": jnp.ones((s, k, p), jnp.float32),
    }


def _build_engine(strategy: str, *, capture: bool = False):
    import dataclasses

    from repro.models.dnn import dnn_hidden
    from repro.train.engine import Engine, TrainState, data_mesh
    from repro.train.train_step import dnn_ssl_grads

    cfg, params, hyper, opt = _tiny_problem()
    mesh = data_mesh(1) if strategy == "sync_mesh" else None

    def grad_fn(p, batch):
        return dnn_ssl_grads(p, batch, cfg=cfg, hyper=hyper, mesh=mesh)

    def step_fn(state, batch, lr):
        # fold_in, not split: the carried key advances per step without a
        # split whose sibling nobody draws from (the R003 shape).
        rng = jax.random.fold_in(state.rng, state.step)
        grads, metrics = grad_fn(state.params, batch)
        new_params, new_opt = opt.update(grads, state.opt_state,
                                         state.params, lr)
        return dataclasses.replace(state, params=new_params,
                                   opt_state=new_opt, rng=rng,
                                   step=state.step + 1), metrics

    kwargs = dict(strategy=strategy)
    if mesh is not None:
        kwargs["mesh"] = mesh
    if capture:
        kwargs["capture_fn"] = lambda p, b: dnn_hidden(
            p, b["x"].reshape(-1, cfg.input_dim))
    if strategy == "async_ps":
        kwargs.update(grad_fn=grad_fn, opt=opt, n_workers=2)
        kwargs.pop("strategy")
        engine = Engine(strategy=strategy, **kwargs)
    else:
        engine = Engine(step_fn, **kwargs)

    state = TrainState.create(params, opt.init(params),
                              jax.random.PRNGKey(1))
    carry = engine.strategy.init_carry(engine.strategy.place_state(state))
    batches = engine.strategy.place_batch(_tiny_batches())
    lr = jnp.float32(0.1)

    def chunk(carry, batches, lr):
        return engine._chunk_fn(carry, batches, lr, capture)

    return chunk, (carry, batches, lr)


# ------------------------------------------------------------------- serve
def _build_serve_decode():
    """``serve/decode.generate`` under sampling (temperature > 0).

    This is the surface the pre-PR-9 RNG bug lived on — prefill reusing
    the unsplit sampling key — and it sat *outside* the audited set.  The
    R-pass now proves the fixed contract on every run: prefill draws
    nothing, the decode loop consumes exactly one fresh subkey per step.
    Sampling must be on (temperature > 0): at temperature 0 the argmax
    path never consumes the key and the whole stream discipline would be
    vacuously untested.
    """
    from repro.configs import get_config
    from repro.models import transformer as tf
    from repro.serve.decode import generate

    cfg = get_config("qwen1.5-0.5b").reduced()
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    prompt = jnp.zeros((1, 3), jnp.int32)

    def run(params, prompt):
        return generate(params, cfg, prompt, steps=3, cache_len=16,
                        temperature=0.7)

    return run, (params, prompt)


# ----------------------------------------------------------------- entries
graph_reg_fused = EntryPoint(
    name="graph_reg_fused", build=_build_fused,
    B=_B, expect_bxb=0)

graph_reg_blocksparse = EntryPoint(
    name="graph_reg_blocksparse", build=_build_blocksparse,
    B=_B, expect_bxb=0)

graph_reg_ref = EntryPoint(
    name="graph_reg_ref", build=_build_ref,
    B=_B, expect_bxb=None, canary_min_bxb=3)

knn_topk = EntryPoint(
    name="knn_topk", build=_build_knn,
    B=_B, expect_bxb=0)

online_refresh = EntryPoint(
    name="online_refresh", build=_build_online_refresh,
    B=_B, expect_bxb=0)

ssl_objective = EntryPoint(
    name="ssl_objective", build=_build_ssl_objective,
    B=_B, expect_bxb=0)

engine_sequential = EntryPoint(
    name="engine_sequential",
    build=lambda: _build_engine("sequential"),
    donate=("_run_chunk", None))

engine_sync_mesh = EntryPoint(
    name="engine_sync_mesh",
    build=lambda: _build_engine("sync_mesh"),
    donate=("_run_chunk", None),
    mesh_axes=(MESH_AXIS,))

engine_async_ps = EntryPoint(
    name="engine_async_ps",
    build=lambda: _build_engine("async_ps"),
    donate=("_run_chunk", None))

engine_capture = EntryPoint(
    name="engine_capture",
    build=lambda: _build_engine("sequential", capture=True),
    donate=("_run_chunk", None))

serve_decode_generate = EntryPoint(
    name="serve_decode_generate",
    build=_build_serve_decode)

#: Audit order (fast kernel traces first, engine traces last).
ENTRY_POINTS = (
    graph_reg_fused,
    graph_reg_blocksparse,
    graph_reg_ref,
    knn_topk,
    online_refresh,
    ssl_objective,
    engine_sequential,
    engine_sync_mesh,
    engine_async_ps,
    engine_capture,
    serve_decode_generate,
)
