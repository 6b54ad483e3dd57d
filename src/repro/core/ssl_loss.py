"""Graph-regularized semi-supervised objective (paper Eq. 2 / Eq. 3), in JAX.

Eq. 2 (full KL form):

    J(θ) = Σ_{i∈labeled} D(t_i ‖ p_i)
         + γ Σ_{i,j} ω_ij D(p_i ‖ p_j)
         + κ Σ_i D(p_i ‖ u)
         + λ ‖θ‖²

Eq. 3 (entropy/cross-entropy decomposition, constants w.r.t. θ dropped):

    J_i = Hc(t_i, p_i) + γ Σ_j ω_ij Hc(p_i, p_j)
        − (κ + γ Σ_j ω_ij) H(p_i) + λ‖θ‖²

All functions take *logits* and work in log-space for stability.  The dense
``W`` block is the (meta-)batch's affinity sub-matrix — dense by construction
after graph partitioning (paper Fig. 1b); the pairwise contraction
``Σ_ij W_ij Hc(p_i,p_j)`` is the compute hot-spot and has fused Pallas
kernels in ``repro.kernels.graph_reg`` — select by name via
``pairwise="pallas"`` (cross term), ``"fused"`` (the whole regularizer in
one sweep) or ``"auto"`` (fused on TPU, jnp oracle elsewhere), resolved
through the ``repro.api.registry.PAIRWISE`` registry.  ``pairwise=None``
keeps the inline jnp oracle; an already-resolved callable passes through
unchanged (resolve once, pass the callable down).
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

from repro.tracing import scope

__all__ = [
    "SSLHyper",
    "entropy",
    "pairwise_cross_entropy_term",
    "graph_regularizer",
    "ssl_objective",
    "ssl_objective_kl_form",
    "l2_penalty",
]

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class SSLHyper:
    """Hyper-parameters of Eq. 2 (γ graph, κ entropy, λ ℓ2).

    Frozen and hashable so it can sit in jit closures / static args; all
    three weights must be non-negative (zero disables the term).
    """

    gamma: float = 1e-3
    kappa: float = 1e-4
    weight_decay: float = 1e-5

    def __post_init__(self):
        for name in ("gamma", "kappa", "weight_decay"):
            v = getattr(self, name)
            if not v >= 0:
                raise ValueError(
                    f"SSLHyper.{name} must be >= 0, got {v!r}")


def _resolve_pairwise(pairwise: str | Callable | None) -> Callable | None:
    """Registry-name lookup (None -> inline jnp oracle).

    Already-resolved callables (and None) short-circuit without touching the
    registry, so callers can resolve once and pass the callable down.
    """
    if pairwise is None or callable(pairwise):
        return pairwise
    from repro.api.registry import resolve_pairwise  # lazy: avoids cycle
    return resolve_pairwise(pairwise)


def entropy(logp: Array) -> Array:
    """Shannon entropy H(p_i) per row from log-probabilities."""
    p = jnp.exp(logp)
    return -jnp.sum(p * logp, axis=-1)


def pairwise_cross_entropy_term(logp: Array, W: Array) -> Array:
    """Σ_ij W_ij · Hc(p_i, p_j)  with  Hc(p_i,p_j) = −Σ_c p_ic log p_jc.

    Computed as a dense matrix product  −Σ (W ⊙ (P · logPᵀ))  — the paper's
    "efficient matrix-matrix multiplication" formulation (§1.1).  This is
    the pure-jnp oracle; the Pallas kernel tiles the same contraction.
    """
    p = jnp.exp(logp)
    S = p @ logp.T                     # S_ij = Σ_c p_ic log p_jc  (B×B)
    return -jnp.sum(W * S)


def graph_regularizer(
    logp: Array,
    W: Array,
    gamma: float,
    kappa: float,
    *,
    pairwise: str | Callable | None = None,
    layout=None,
) -> Array:
    """γ Σ_ij W_ij Hc(p_i,p_j) − (κ + γ Σ_j W_ij) H(p_i)   (Eq. 4 + entropy reg).

    ``pairwise`` selects the contraction implementation by registry name
    ("ref" | "pallas" | "fused" | "auto"); ``None`` uses the inline jnp
    oracle.  Implementations carrying the ``full_regularizer`` marker (the
    fused single-pass kernel) compute the *whole* penalty — cross term, row
    degrees and entropy correction — in one sweep, so the separate jnp
    degree/entropy passes below are skipped entirely.

    ``layout`` is the batch's block-sparsity descriptor — the flat array
    tuple from ``BlockLayout.arrays()`` (or the ``BlockLayout`` itself) —
    forwarded only to implementations advertising ``accepts_layout`` (the
    block-sparse kernel and "auto"); others ignore it.
    Returns the summed (not averaged) penalty over the batch.
    """
    impl = _resolve_pairwise(pairwise)
    if impl is not None and getattr(impl, "full_regularizer", False):
        if layout is not None and getattr(impl, "accepts_layout", False):
            return impl(logp, W, gamma, kappa, layout=layout)
        return impl(logp, W, gamma, kappa)
    impl = impl or pairwise_cross_entropy_term
    cross = impl(logp, W)
    deg = jnp.sum(W, axis=1)                     # Σ_j ω_ij
    h = entropy(logp)
    return gamma * cross - jnp.sum((kappa + gamma * deg) * h)


def l2_penalty(params) -> Array:
    leaves = jax.tree_util.tree_leaves(params)
    return sum(jnp.sum(jnp.square(x)) for x in leaves) if leaves else jnp.float32(0)


def ssl_objective(
    logits: Array,
    labels: Array,
    label_mask: Array,
    W: Array,
    hyper: SSLHyper,
    *,
    params=None,
    pairwise: str | Callable | None = None,
    layout=None,
    reduction: str = "mean",
) -> tuple[Array, dict]:
    """Decomposed Eq.-3 objective over one (concatenated meta-)batch.

    Args:
      logits: (B, C) unnormalized outputs.
      labels: (B,) int class ids; entries where ``label_mask == 0`` ignored.
      label_mask: (B,) {0,1} — 1 for labeled points (semi-supervised).
      W: (B, B) dense affinity block for this batch.
      pairwise: pairwise-kernel registry name ("ref" | "pallas" | "fused" |
        "auto") or a ``(logp, W) -> scalar`` callable; None = inline jnp
        oracle.  "fused"/"auto" compute the whole graph regularizer in one
        Pallas sweep (see ``graph_regularizer``).
      layout: optional block-sparsity descriptor of ``W`` (the array tuple
        from ``BlockLayout.arrays()``), forwarded to layout-aware pairwise
        implementations so the kernel skips structurally-zero tiles.
      reduction: 'sum' is the paper-faithful Eq. 2; 'mean' normalizes the
        supervised term by #labeled and the graph terms by B (scale-stable
        across batch sizes; used by the trainer).

    Returns (loss, metrics-dict).
    """
    # Resolve the registry name exactly once; graph_regularizer passes the
    # already-resolved callable straight through (no second lookup).
    pairwise = _resolve_pairwise(pairwise)
    logp = jax.nn.log_softmax(logits, axis=-1)
    # Supervised term: Hc(t_i, p_i) over labeled points (t one-hot => CE).
    picked = jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32), axis=1)[:, 0]
    sup = -jnp.sum(picked * label_mask)
    n_labeled = jnp.maximum(jnp.sum(label_mask), 1.0)
    with scope("graph_reg"):
        greg = graph_regularizer(logp, W, hyper.gamma, hyper.kappa,
                                 pairwise=pairwise, layout=layout)
    l2 = hyper.weight_decay * l2_penalty(params) if params is not None else jnp.float32(0)
    if reduction == "mean":
        b = logits.shape[0]
        loss = sup / n_labeled + greg / b + l2
    else:
        loss = sup + greg + l2
    metrics = {
        "loss/supervised": sup / n_labeled,
        "loss/graph": greg,
        "loss/l2": l2,
        "acc/labeled": jnp.sum(
            (jnp.argmax(logits, -1) == labels) * label_mask) / n_labeled,
    }
    return loss, metrics


def ssl_objective_kl_form(
    logits: Array,
    labels: Array,
    label_mask: Array,
    W: Array,
    hyper: SSLHyper,
    *,
    params=None,
) -> Array:
    """Literal Eq.-2 KL form (sum reduction) — used to *test* that the Eq.-3
    decomposition equals Eq. 2 up to constants w.r.t. θ."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    p = jnp.exp(logp)
    n, c = logits.shape
    onehot = jax.nn.one_hot(labels, c)
    # D(t||p) with one-hot t: -log p[label]  (H(t)=0).
    sup = -jnp.sum(jnp.sum(onehot * logp, axis=-1) * label_mask)
    # D(p_i||p_j) = Σ_c p_ic (log p_ic - log p_jc).
    kl_ij = (jnp.sum(p * logp, axis=-1)[:, None]) - (p @ logp.T)
    graph = jnp.sum(W * kl_ij)
    # D(p||u) = log C - H(p).
    ent = jnp.sum(jnp.log(jnp.float32(c)) - entropy(logp))
    l2 = l2_penalty(params) if params is not None else jnp.float32(0)
    return sup + hyper.gamma * graph + hyper.kappa * ent + hyper.weight_decay * l2
