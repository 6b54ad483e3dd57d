"""Blocked pairwise-distance kernels for graph construction (§3).

Two device paths:

``rbf_affinity_pallas``
    Dense affinity tile  w_ij = exp(−‖x_i − x_j‖ / 2σ²)  for a block of the
    k-NN candidate matrix:  ‖x_i − x_j‖² = n_i − 2·x_iᵀx_j + n_j  with the
    inner product tiled over the feature dimension on the MXU and the row
    norms passed in precomputed.  Materializes the full (N, M) block — fine
    for a (meta-)batch, ruinous for corpus-scale k-NN search.

``knn_topk_pallas``
    Streaming top-k: tiles over *candidate columns* and keeps a running
    per-row top-k (squared distance + column index) in VMEM scratch, so the
    (N, M) distance matrix is never materialized anywhere — the working set
    is one (bi, bj) tile plus the (bi, k) running state.  Per column chunk
    the k best candidates are folded in by k predicated min-extraction
    steps (k ≈ 10 ≪ bj, so the merge is noise next to the MXU contraction).

  grid = (N/bi, M/bj, D/bd);  VMEM scratch accumulates the (bi, bj) inner-
  product tile over feature chunks; the last chunk applies norms (+ RBF or
  the top-k merge).  ``interpret=None`` derives the mode from the backend:
  compiled on TPU, interpreter elsewhere.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tuning import TileSpec, select_tiles
from .tuning import default_interpret as _default_interpret

DEFAULT_BI = 128
DEFAULT_BJ = 128
DEFAULT_BD = 256

#: Full f32 MXU contractions: Mosaic's default for f32 operands is one bf16
#: pass, too coarse for exact neighbour sets (see ``graph_reg._F32``).
_F32 = jax.lax.Precision.HIGHEST
_BIG = 3.4e38                       # "+inf" that survives arithmetic
_BIG_POS = 2 ** 30


def _pairwise_kernel(x_ref, y_ref, nx_ref, ny_ref, sig_ref, out_ref, acc_ref,
                     *, n_d_blocks: int):
    di = pl.program_id(2)

    @pl.when(di == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], y_ref[...], (((1,), (1,)), ((), ())),
        precision=_F32, preferred_element_type=jnp.float32)

    @pl.when(di == n_d_blocks - 1)
    def _finish():
        d2 = nx_ref[...] - 2.0 * acc_ref[...] + ny_ref[...].T
        d2 = jnp.maximum(d2, 0.0)
        sigma = sig_ref[0, 0]
        out_ref[...] = jnp.exp(-jnp.sqrt(d2) / (2.0 * sigma * sigma))


@functools.partial(jax.jit, static_argnames=("bi", "bj", "bd", "interpret"))
def rbf_affinity_pallas(
    x: jax.Array, y: jax.Array, sigma: jax.Array | float, *,
    bi: int = DEFAULT_BI, bj: int = DEFAULT_BJ, bd: int = DEFAULT_BD,
    interpret: bool | None = None,
) -> jax.Array:
    """Dense RBF affinity block. x: (N, D); y: (M, D) -> (N, M)."""
    interpret = _default_interpret(interpret)
    N, D = x.shape
    M = y.shape[0]
    bi, bj, bd = min(bi, N), min(bj, M), min(bd, D)
    pi, pj, pd = (-N) % bi, (-M) % bj, (-D) % bd
    xp = jnp.pad(x, ((0, pi), (0, pd)))
    yp = jnp.pad(y, ((0, pj), (0, pd)))
    nx = jnp.sum(xp.astype(jnp.float32) ** 2, axis=1, keepdims=True)
    ny = jnp.sum(yp.astype(jnp.float32) ** 2, axis=1, keepdims=True)
    grid = ((N + pi) // bi, (M + pj) // bj, (D + pd) // bd)
    sig = jnp.full((1, 1), sigma, jnp.float32)
    out = pl.pallas_call(
        functools.partial(_pairwise_kernel, n_d_blocks=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bi, bd), lambda i, j, d: (i, d)),
            pl.BlockSpec((bj, bd), lambda i, j, d: (j, d)),
            pl.BlockSpec((bi, 1), lambda i, j, d: (i, 0)),
            pl.BlockSpec((bj, 1), lambda i, j, d: (j, 0)),
            pl.BlockSpec((1, 1), lambda i, j, d: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bi, bj), lambda i, j, d: (i, j)),
        out_shape=jax.ShapeDtypeStruct((N + pi, M + pj), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bi, bj), jnp.float32)],
        name="rbf_affinity",
        interpret=interpret,
    )(xp.astype(jnp.float32), yp.astype(jnp.float32), nx, ny, sig)
    return out[:N, :M]


# ---------------------------------------------------------------------------
# Streaming top-k (never materializes the N×M distance matrix).
# ---------------------------------------------------------------------------
def _topk_kernel(x_ref, y_ref, nx_ref, ny_ref, out_d2_ref, out_idx_ref,
                 acc_ref, best_d2_ref, best_idx_ref, *,
                 k: int, n_cols: int, n_j: int, n_d: int,
                 exclude_self: bool, bi: int, bj: int):
    i, j, d = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when((j == 0) & (d == 0))
    def _init_best():
        best_d2_ref[...] = jnp.full_like(best_d2_ref, _BIG)
        best_idx_ref[...] = jnp.full_like(best_idx_ref, -1)

    @pl.when(d == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], y_ref[...], (((1,), (1,)), ((), ())),
        precision=_F32, preferred_element_type=jnp.float32)

    @pl.when(d == n_d - 1)
    def _merge_chunk():
        d2 = jnp.maximum(nx_ref[...] - 2.0 * acc_ref[...] + ny_ref[...].T,
                         0.0)
        col = j * bj + jax.lax.broadcasted_iota(jnp.int32, (bi, bj), 1)
        d2 = jnp.where(col >= n_cols, _BIG, d2)          # padded columns
        if exclude_self:
            row = (i * bi
                   + jax.lax.broadcasted_iota(jnp.int32, (bi, bj), 0))
            d2 = jnp.where(col == row, _BIG, d2)
        # Fold the chunk into the running top-k: k predicated min-extraction
        # steps over the (bi, k + bj) candidate set (values live in
        # registers/VMEM only — nothing is written back per chunk).
        cand_val = jnp.concatenate([best_d2_ref[...], d2], axis=1)
        cand_idx = jnp.concatenate([best_idx_ref[...], col], axis=1)
        pos = jax.lax.broadcasted_iota(jnp.int32, cand_val.shape, 1)
        new_val, new_idx = [], []
        for _ in range(k):
            m = jnp.min(cand_val, axis=1, keepdims=True)
            # First (lowest-position) occurrence of the minimum — keeps tie
            # order stable, matching lax.top_k on the dense oracle.
            sel = jnp.min(jnp.where(cand_val == m, pos, _BIG_POS),
                          axis=1, keepdims=True)
            hit = pos == sel
            new_val.append(m[:, 0])
            new_idx.append(jnp.sum(jnp.where(hit, cand_idx, 0), axis=1))
            cand_val = jnp.where(hit, _BIG, cand_val)
        best_d2_ref[...] = jnp.stack(new_val, axis=1)
        best_idx_ref[...] = jnp.stack(new_idx, axis=1)

    @pl.when((j == n_j - 1) & (d == n_d - 1))
    def _flush():
        out_d2_ref[...] = best_d2_ref[...]
        out_idx_ref[...] = best_idx_ref[...]


@functools.partial(jax.jit, static_argnames=("k", "exclude_self", "bi", "bj",
                                             "bd", "interpret"))
def _knn_topk(x, y, *, k, exclude_self, bi, bj, bd, interpret):
    N, D = x.shape
    M = y.shape[0]
    pi, pj, pd = (-N) % bi, (-M) % bj, (-D) % bd
    xp = jnp.pad(x, ((0, pi), (0, pd)))
    yp = jnp.pad(y, ((0, pj), (0, pd)))
    nx = jnp.sum(xp.astype(jnp.float32) ** 2, axis=1, keepdims=True)
    ny = jnp.sum(yp.astype(jnp.float32) ** 2, axis=1, keepdims=True)
    grid = ((N + pi) // bi, (M + pj) // bj, (D + pd) // bd)
    d2, idx = pl.pallas_call(
        functools.partial(_topk_kernel, k=k, n_cols=M, n_j=grid[1],
                          n_d=grid[2], exclude_self=exclude_self,
                          bi=bi, bj=bj),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bi, bd), lambda i, j, d: (i, d)),
            pl.BlockSpec((bj, bd), lambda i, j, d: (j, d)),
            pl.BlockSpec((bi, 1), lambda i, j, d: (i, 0)),
            pl.BlockSpec((bj, 1), lambda i, j, d: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bi, k), lambda i, j, d: (i, 0)),
            pl.BlockSpec((bi, k), lambda i, j, d: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N + pi, k), jnp.float32),
            jax.ShapeDtypeStruct((N + pi, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bi, bj), jnp.float32),   # inner-product tile
            pltpu.VMEM((bi, k), jnp.float32),    # running top-k distances
            pltpu.VMEM((bi, k), jnp.int32),      # running top-k indices
        ],
        name="knn_topk",
        interpret=interpret,
    )(xp.astype(jnp.float32), yp.astype(jnp.float32), nx, ny)
    return d2[:N], idx[:N]


def knn_topk_pallas(
    x: jax.Array, y: jax.Array, k: int, *,
    exclude_self: bool = False,
    bi: int | None = None, bj: int | None = None, bd: int | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Streaming k-NN: per-row k smallest squared distances and indices.

    x: (N, D) queries; y: (M, D) candidates → ``(d2, idx)`` of shape (N, k),
    sorted ascending.  ``exclude_self`` masks the diagonal (x is y).  The
    candidate axis is streamed in bj-wide chunks — peak memory is
    O(N·k + bi·bj), independent of M.
    """
    N, D = x.shape
    M = y.shape[0]
    limit = M - 1 if exclude_self else M
    if not 0 < k <= limit:
        raise ValueError(f"k must be in [1, {limit}] for M={M} candidates "
                         f"(exclude_self={exclude_self}), got {k}")
    auto = select_tiles("topk", rows=N, pinned=TileSpec(bi=bi, bj=bj, bd=bd))
    bi = min(auto.bi or DEFAULT_BI, N)
    bj = min(auto.bj or 512, M)
    bd = min(auto.bd or DEFAULT_BD, D)
    return _knn_topk(x, y, k=k, exclude_self=exclude_self, bi=bi, bj=bj,
                     bd=bd, interpret=_default_interpret(interpret))
