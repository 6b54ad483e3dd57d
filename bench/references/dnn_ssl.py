"""Plain reference of the paper's graph-SSL training (arXiv:1612.04898).

Written from the paper's equations and the configuration file alone, in
straightforward ``jax.numpy`` and ``numpy``; it imports nothing of the
program under test.

* Graph (``knn_rbf_graph``): exact k-NN over the corpus features by brute
  force (float32 squared distances, the self excluded), the RBF weight
  ``exp(-||x_i - x_j|| / (2 sigma^2))`` with ``sigma`` the mean distance to
  the k-th neighbour, symmetrized by the larger weight of the two
  directions, zero diagonal.
* DNN: ``input_dim -> n_hidden x hidden_dim -> n_classes``, ReLU, inverted
  dropout after every hidden layer, He-normal weights and zero biases drawn
  from the model seed (the seed's first split is kept as the step key, its
  second initialises the layers, one key per layer).
* Per step the key splits into the next key and the step's dropout key;
  each hidden layer splits that key once more for its mask.
* Loss of one worker's concatenated batch of ``P`` padded rows (Eq. 3,
  mean form): cross-entropy over labelled valid rows divided by their
  count, plus ``greg / P`` with ``greg = gamma * sum_ij W_ij Hc(p_i, p_j) -
  sum_i (kappa + gamma deg_i) H(p_i)``, plus ``weight_decay * ||theta||^2``.
  Padding rows carry no affinity and no label.  With k workers the loss is
  their mean.
* AdaGrad: ``G += g^2``, ``theta -= lr * g / (sqrt(G) + 1e-8)``.

Arithmetic.  The reference computes what the configuration states: the
DNN's matmuls with their operands rounded to ``dnn_operands`` (one MXU
pass: the rounded values multiplied exactly, the products summed in
float32, in the forward pass and in both matmuls of its backward pass),
everything else in float32, the regularizer's contraction at ``highest``.
``CONTROLS`` names the arithmetic put in the program's place to show that
the check fails below what the configuration states; ``fault`` plants one
of the faults a training step can have, for the calibration and the tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import scipy.sparse as sp

EPS = 1e-8
#: Faults the reference can carry in the program's place.
FAULTS = ("unchanged", "half_batch", "no_exchange")
#: Arithmetic below the stated precision, each put in the program's place:
#: ``step`` holds keyword arguments of ``run_steps``, ``kernel`` those of
#: ``regularizer_value_and_grad``.  ``control``: one step below every
#: stated precision (the DNN's operands, bfloat16 in the program, rounded
#: to float8; the regularizer's contraction, stated at ``highest``, at
#: ``high``).  ``reg_one_pass``: the stated DNN arithmetic with the
#: regularizer's contraction at one bfloat16 pass.  ``bf16_compute``:
#: activations and every contraction in bfloat16.
CONTROLS = {
    "control": {"step": {"dnn_round": "float8_e4m3fn",
                         "reg_precision": "high"},
                "kernel": {"precision": "high"}},
    "reg_one_pass": {"step": {"reg_round": "bfloat16"},
                     "kernel": {"round_to": "bfloat16"}},
    "bf16_compute": {"step": {"dtype": "bfloat16", "dnn_round": None,
                              "precision": None, "reg_precision": None},
                     "kernel": {"round_to": "bfloat16"}},
}
_DTYPES = {None: None, "float32": None, "bfloat16": jnp.bfloat16,
           "float8_e4m3fn": jnp.float8_e4m3fn}
_HIGHEST = jax.lax.Precision.HIGHEST


# ------------------------------------------------------------------ graph
@functools.partial(jax.jit, static_argnames=("k",))
def _topk_block(q, X, sq, first, *, k):
    d2 = (jnp.sum(q * q, axis=1)[:, None]
          - 2.0 * jnp.matmul(q, X.T, precision=_HIGHEST) + sq[None, :])
    d2 = jnp.maximum(d2, 0.0)
    rows = jnp.arange(q.shape[0])
    d2 = d2.at[rows, first + rows].set(jnp.inf)
    neg, idx = jax.lax.top_k(-d2, k)
    return -neg, idx


def knn_rbf_graph(X: np.ndarray, k: int, block: int = 2048
                  ) -> sp.csr_matrix:
    """The symmetric RBF-weighted k-NN affinity matrix of ``X`` (n, d),
    its distances taken ``block`` rows at a time on the default device."""
    n = X.shape[0]
    Xd = jnp.asarray(np.asarray(X, np.float32))
    sq = jnp.sum(Xd * Xd, axis=1)
    d2s, idxs = [], []
    for s in range(0, n, block):
        q = Xd[s:s + block]
        pad = block - q.shape[0]
        if pad:
            q = jnp.pad(q, ((0, pad), (0, 0)))
        d2, idx = _topk_block(q, Xd, sq, jnp.int32(s), k=k)
        d2s.append(d2[:block - pad])
        idxs.append(idx[:block - pad])
    d2, idx = jax.device_get((jnp.concatenate(d2s), jnp.concatenate(idxs)))
    dist = np.sqrt(np.asarray(d2, np.float32))
    sigma = float(np.mean(dist[:, -1])) or 1.0
    w = np.exp(-dist / np.float32(2.0 * sigma * sigma)).astype(np.float32)
    W = sp.csr_matrix((w.ravel(), (np.repeat(np.arange(n), k),
                                   np.asarray(idx).ravel())), shape=(n, n))
    W = W.maximum(W.T).tocsr()
    W.setdiag(0.0)
    W.eliminate_zeros()
    return W


# -------------------------------------------------------------------- DNN
def dims_of(cfg: dict) -> list[int]:
    return ([cfg["input_dim"]] + [cfg["hidden_dim"]] * cfg["n_hidden"]
            + [cfg["n_classes"]])


def init(cfg: dict, model_seed: int):
    """``(params, step_key)``: params a list of ``(w, b)`` in float32."""
    key = jax.random.PRNGKey(model_seed)
    key, init_key = jax.random.split(key)
    dims = dims_of(cfg)
    ks = jax.random.split(init_key, len(dims) - 1)
    params = [(jax.random.normal(ks[i], (dims[i], dims[i + 1]), jnp.float32)
               * (2.0 / dims[i]) ** 0.5,
               jnp.zeros((dims[i + 1],), jnp.float32))
              for i in range(len(dims) - 1)]
    return params, key


def _rounded(a, dtype):
    """``a`` rounded to ``dtype`` and back (``dtype`` None: unchanged)."""
    return a if dtype is None else a.astype(dtype).astype(a.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _one_pass(a, b, dtype):
    """``a @ b`` in one pass of rounded operands, forward and backward."""
    return jnp.matmul(_rounded(a, dtype), _rounded(b, dtype),
                      precision=_HIGHEST)


def _one_pass_fwd(a, b, dtype):
    return _one_pass(a, b, dtype), (a, b)


def _one_pass_bwd(dtype, res, g):
    a, b = res
    return (_one_pass(g, b.T, dtype), _one_pass(a.T, g, dtype))


_one_pass.defvjp(_one_pass_fwd, _one_pass_bwd)


def _greg(logp, W, gamma, kappa, precision, round_to):
    """``gamma * sum_ij W_ij Hc(p_i, p_j) - sum_i (kappa + gamma deg_i)
    H(p_i)``, the cross term's contraction at ``precision`` on operands
    rounded to ``round_to``."""
    p = jnp.exp(logp)
    S = jnp.matmul(_rounded(p, round_to), _rounded(logp, round_to).T,
                   precision=precision)
    deg = jnp.sum(W, axis=1)
    ent = -jnp.sum(p * logp, axis=-1)
    return -gamma * jnp.sum(W * S) - jnp.sum((kappa + gamma * deg) * ent)


def _worker_loss(params, key, x, y, mask, W, valid, *, cfg, dtype,
                 precision, dnn_round, reg_precision, reg_round):
    """``(loss, greg)`` of one worker's batch; ``greg`` the summed
    regularizer of Eq. 3/4."""
    rate = cfg["dropout"]
    h = x.astype(dtype)
    for i, (w, b) in enumerate(params):
        w = w.astype(dtype)
        if dnn_round is None:
            h = jnp.matmul(h, w, precision=precision)
        else:
            h = _one_pass(h, w, dnn_round)
        h = h + b.astype(dtype)
        if i < len(params) - 1:
            h = jnp.where(h > 0, h, 0)
            if rate > 0:
                key, sub = jax.random.split(key)
                keep = jax.random.bernoulli(sub, 1.0 - rate, h.shape)
                h = jnp.where(keep, h / (1.0 - rate), 0)
    logp = jax.nn.log_softmax(h, axis=-1)
    v = valid.astype(dtype)
    m = mask.astype(dtype) * v
    Wm = W.astype(dtype) * v[:, None] * v[None, :]
    picked = jnp.take_along_axis(logp, y[:, None].astype(jnp.int32), 1)[:, 0]
    sup = -jnp.sum(picked * m)
    n_lab = jnp.maximum(jnp.sum(m), 1)
    greg = _greg(logp, Wm, cfg["gamma"], cfg["kappa"], reg_precision,
                 reg_round).astype(jnp.float32)
    l2 = sum(jnp.sum(jnp.square(w)) + jnp.sum(jnp.square(b))
             for w, b in params)
    loss = (sup / n_lab).astype(jnp.float32) + greg / x.shape[0] \
        + cfg["weight_decay"] * l2
    return loss, greg


@functools.partial(jax.jit, static_argnames=(
    "cfg_items", "dtype", "precision", "dnn_round", "reg_precision",
    "reg_round", "fault"))
def _step(params, accum, key, x, y, mask, W, valid, lr, *, cfg_items,
          dtype, precision, dnn_round, reg_precision, reg_round, fault):
    cfg = dict(cfg_items)
    key, sub = jax.random.split(key)
    if fault == "half_batch":
        # Half of each worker's rows left out; the mean over the rest.
        rank = jnp.cumsum(valid.astype(jnp.int32), axis=-1)
        half = jnp.sum(valid, axis=-1, keepdims=True) // 2
        valid = valid & (rank <= half)

    def loss_fn(ps):
        per, greg = jax.vmap(functools.partial(
            _worker_loss, cfg=cfg, dtype=dtype, precision=precision,
            dnn_round=dnn_round, reg_precision=reg_precision,
            reg_round=reg_round),
            in_axes=(None, None, 0, 0, 0, 0, 0))(ps, sub, x, y, mask, W,
                                                  valid)
        reported = (jnp.mean(per), jnp.mean(greg))
        if fault == "no_exchange":
            # Each chip keeps its own gradient; chip 0's state is worker 0's.
            return per[0], reported
        return jnp.mean(per), reported

    (_, reported), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
    if fault == "unchanged":
        # The step returns its state as it got it.
        return params, accum, key, reported
    accum = jax.tree.map(lambda a, g: a + jnp.square(g.astype(jnp.float32)),
                         accum, grads)
    params = jax.tree.map(
        lambda p, g, a: p - lr * g.astype(jnp.float32) / (jnp.sqrt(a) + EPS),
        params, grads, accum)
    return params, accum, key, reported


def _precision(name):
    return getattr(jax.lax.Precision, name.upper()) if name else None


@functools.partial(jax.jit, static_argnames=("dnn_round",))
def _seed_logp(params, x, *, dnn_round):
    h = x
    for i, (w, b) in enumerate(params):
        h = (jnp.matmul(h, w, precision=_HIGHEST) if dnn_round is None
             else _one_pass(h, w, dnn_round)) + b
        if i < len(params) - 1:
            h = jnp.where(h > 0, h, 0)
    return jax.nn.log_softmax(h, axis=-1)


def seed_logp(cfg: dict, model_seed: int, x: np.ndarray):
    """Log-probabilities of the rows ``x`` (P, d) under the seed's
    parameters, without dropout, in the stated arithmetic."""
    params, _ = init(cfg, model_seed)
    return _seed_logp(params, jnp.asarray(np.asarray(x, np.float32)),
                      dnn_round=_DTYPES[cfg["dnn_operands"]])


@functools.partial(jax.jit, static_argnames=("gamma", "kappa", "precision",
                                             "round_to"))
def _reg_value_and_grad(logp, W, *, gamma, kappa, precision, round_to):
    return jax.value_and_grad(_greg)(logp, W, gamma, kappa,
                                     _precision(precision), _DTYPES[round_to])


def regularizer_value_and_grad(logp, W, *, gamma: float, kappa: float,
                               precision: str = "highest",
                               round_to: str | None = None):
    """``greg`` of Eq. 3/4 over one block (``logp`` (P, C), ``W`` (P, P),
    padding already without weight) and its gradient by ``logp``."""
    return _reg_value_and_grad(logp, W, gamma=float(gamma),
                               kappa=float(kappa), precision=precision,
                               round_to=round_to)


_STATED = object()


def run_steps(cfg: dict, model_seed: int, chunk: dict, *, lr: float,
              dtype: str = "float32", precision: str | None = "highest",
              dnn_round=_STATED, reg_precision: str | None = "highest",
              reg_round: str | None = None, fault: str | None = None,
              device=None) -> dict:
    """Train from the seed over the ``(S, k, ...)`` host ``chunk``, one step
    per leading index.  ``dnn_round`` defaults to the configuration's
    ``dnn_operands``.  Returns host float64 ``losses`` and ``greg`` (the
    summed regularizer, mean over workers), each (S,), and ``params0``,
    ``params`` and ``accum`` (lists of ``(w, b)``)."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    if dnn_round is _STATED:
        dnn_round = cfg["dnn_operands"]
    device = device or jax.devices()[0]
    params0, key = init(cfg, model_seed)
    params0, key = jax.device_put((params0, key), device)
    params = params0
    accum = jax.tree.map(jnp.zeros_like, params)
    cfg_items = tuple(sorted((k, v) for k, v in cfg.items()
                             if isinstance(v, (int, float))))
    losses, gregs = [], []
    for t in range(chunk["x"].shape[0]):
        step_in = jax.device_put(
            tuple(chunk[k][t] for k in ("x", "y", "label_mask", "W",
                                         "valid")), device)
        params, accum, key, (loss, greg) = _step(
            params, accum, key, *step_in, jnp.float32(lr),
            cfg_items=cfg_items, dtype=_DTYPES[dtype] or jnp.float32,
            precision=_precision(precision), dnn_round=_DTYPES[dnn_round],
            reg_precision=_precision(reg_precision),
            reg_round=_DTYPES[reg_round], fault=fault)
        losses.append(loss)
        gregs.append(greg)
    host = jax.device_get((losses, gregs, params0, params, accum))
    return {"losses": np.asarray(host[0], np.float64),
            "greg": np.asarray(host[1], np.float64),
            "params0": host[2], "params": host[3], "accum": host[4]}
