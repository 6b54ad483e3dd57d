"""Fused graph-regularizer kernels (the paper's compute hot-spot, §1.1).

The Eq.-3/4 regularizer over one dense (meta-)batch affinity block is

    L(logp, W) = γ Σ_ij W_ij · Hc(p_i, p_j) − Σ_i (κ + γ Σ_j W_ij) H(p_i)

with Hc(p_i, p_j) = −Σ_c p_ic log p_jc and H(p_i) = −Σ_c p_ic log p_ic.
The paper's efficiency argument is exactly this: graph partitioning makes
the per-batch affinity block W dense, so the regularizer becomes one
matrix-matrix contraction instead of sparse gathers.  On TPU we tile it for
the MXU, and — unlike the historical three-pass path (Pallas cross term,
then jnp degrees, then jnp entropy) — compute *all three terms in a single
grid sweep*:

  grid = (B/bi, B/bj, C/bc), class chunk innermost.  For each (i, j) tile
  the class dimension is accumulated over bc-chunks into a VMEM scratch
  tile (bi × bj, f32); row degrees Σ_j W_ij accumulate once per j-block
  into a (bi, 1) scratch, the per-row entropy accumulates on the j == 0
  pass, and the last chunk of each tile folds everything into the scalar
  output.

The backward pass is analytic and tiled the same way (see
``_reg_bwd_dlogp_kernel`` / ``_reg_bwd_dw_kernel``):

    ∂L/∂logp = γ·[−(P ⊙ (W·logP) + Wᵀ·P)] + (κ + γ·deg) ⊙ P ⊙ (logP + 1)
    ∂L/∂W_ij = −γ·[(P·logPᵀ)_ij + H(p_i)]

so no B×B intermediate is ever materialized outside a kernel.

All kernels take an internal scalar triple ``(gc, κ, ge)`` — cross-term
weight, uniform entropy weight, degree-entropy weight — so the same code
serves both the full regularizer (gc = ge = γ) and the bare pairwise cross
term (gc = 1, κ = ge = 0).

Block sizes default to the ``repro.kernels.tuning`` table — MXU-aligned
(128 lanes) with the class chunk kept wide to amortize the weight-
stationary W tile.  VMEM working set at (128, 128, 512) defaults:
bi·bc + bj·bc + bi·bj + scratch ≈ 0.9 MB.  ``interpret=None`` derives the
mode from the backend: compiled on TPU, interpreter elsewhere.
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
DEFAULT_BC = 512

#: Every MXU contraction asks for full f32 precision.  Mosaic's default for
#: f32 operands is one bf16 pass (a v5e kernel compiled with the default is
#: the size of its bf16-input twin; with HIGHEST it is ~5x larger), which
#: would leave the compiled regularizer ~1e-3 off its f32 reference.
#: Interpret mode computes in f32 either way, so no CPU test can see it.
_F32 = jax.lax.Precision.HIGHEST

#: Whole-array scalar memory: the forward kernels' (1, 4) parameter input
#: and their (1, 1) accumulated output.  Mosaic cannot store a scalar into a
#: VMEM block, so the loss accumulator lives in SMEM.
_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _pad2(a: jax.Array, pr: int, pc: int) -> jax.Array:
    return jnp.pad(a, ((0, pr), (0, pc))) if (pr or pc) else a


def _reg_tiles(B: int, C: int, bi, bj, bc) -> tuple[int, int, int]:
    """Table-selected tiles with explicit overrides, clamped to the shape."""
    auto = select_tiles("graph_reg", rows=B,
                        pinned=TileSpec(bi=bi, bj=bj, bc=bc))
    return (min(auto.bi or DEFAULT_BI, B), min(auto.bj or DEFAULT_BJ, B),
            min(auto.bc or DEFAULT_BC, C))


# ---------------------------------------------------------------------------
# Forward: single-pass fused regularizer.
# ---------------------------------------------------------------------------
def _graph_reg_kernel(p_ref, logp_ref, w_ref, out_ref, acc_ref, *,
                      n_c_blocks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init_tile():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # S_tile += P_i(bi, bc) @ logP_j(bj, bc)^T   — MXU contraction.
    acc_ref[...] += jax.lax.dot_general(
        p_ref[...], logp_ref[...],
        (((1,), (1,)), ((), ())),
        precision=_F32, preferred_element_type=jnp.float32)

    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when((i == 0) & (j == 0) & (ci == 0))
    def _init_out():
        out_ref[0, 0] = 0.0

    @pl.when(ci == n_c_blocks - 1)
    def _finish_tile():
        # cross = −Σ W ⊙ S  (accumulated over all (i, j) tiles).
        out_ref[0, 0] += -jnp.sum(w_ref[...] * acc_ref[...])


def _fused_reg_kernel(p_ref, logpj_ref, logpi_ref, w_ref, s_ref, out_ref,
                      acc_ref, deg_ref, ent_ref, *, n_j: int, n_c: int):
    i, j, c = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when((i == 0) & (j == 0) & (c == 0))
    def _init_out():
        out_ref[0, 0] = 0.0

    @pl.when((j == 0) & (c == 0))
    def _init_row_state():
        deg_ref[...] = jnp.zeros_like(deg_ref)
        ent_ref[...] = jnp.zeros_like(ent_ref)

    @pl.when(c == 0)
    def _init_tile():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        # Row degrees: one j-block strip of Σ_j W_ij per tile (W is c-inv).
        deg_ref[...] += jnp.sum(w_ref[...], axis=1, keepdims=True)

    # S_tile += P_i(bi, bc) @ logP_j(bj, bc)^T   — MXU contraction.
    acc_ref[...] += jax.lax.dot_general(
        p_ref[...], logpj_ref[...],
        (((1,), (1,)), ((), ())),
        precision=_F32, preferred_element_type=jnp.float32)

    @pl.when(j == 0)
    def _entropy_chunk():
        # H(p_i) accumulated over class chunks, once per row block (j == 0).
        ent_ref[...] += -jnp.sum(p_ref[...] * logpi_ref[...], axis=1,
                                 keepdims=True)

    gc = s_ref[0, 0]
    kappa = s_ref[0, 1]
    ge = s_ref[0, 2]

    @pl.when(c == n_c - 1)
    def _finish_tile():
        out_ref[0, 0] += -gc * jnp.sum(w_ref[...] * acc_ref[...])

    @pl.when((j == n_j - 1) & (c == n_c - 1))
    def _finish_row_block():
        # −Σ_i (κ + ge·deg_i)·H(p_i) for this row block; deg/ent complete.
        out_ref[0, 0] += -jnp.sum((kappa + ge * deg_ref[...]) * ent_ref[...])


@functools.partial(jax.jit, static_argnames=("bi", "bj", "bc", "interpret"))
def _fused_reg_forward(
    logp: jax.Array, W: jax.Array, scalars: jax.Array, *,
    bi: int, bj: int, bc: int, interpret: bool,
) -> jax.Array:
    B, C = logp.shape
    pad_i, pad_j, pad_c = (-B) % bi, (-B) % bj, (-C) % bc
    # Padding: p rows/cols pad to 0 (so padded entries kill every product);
    # logp pads to 0 as well — 0·logp and p·0 terms all vanish.
    p = _pad2(jnp.exp(logp), pad_i, pad_c)
    logpj = _pad2(logp, pad_j, pad_c)
    logpi = _pad2(logp, pad_i, pad_c)
    Wp = _pad2(W, pad_i, pad_j)
    grid = ((B + pad_i) // bi, (B + pad_j) // bj, (C + pad_c) // bc)
    out = pl.pallas_call(
        functools.partial(_fused_reg_kernel, n_j=grid[1], n_c=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bi, bc), lambda i, j, c: (i, c)),
            pl.BlockSpec((bj, bc), lambda i, j, c: (j, c)),
            pl.BlockSpec((bi, bc), lambda i, j, c: (i, c)),
            pl.BlockSpec((bi, bj), lambda i, j, c: (i, j)),
            _SMEM,
        ],
        out_specs=_SMEM,
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bi, bj), jnp.float32),   # S tile accumulator
            pltpu.VMEM((bi, 1), jnp.float32),    # row degrees
            pltpu.VMEM((bi, 1), jnp.float32),    # row entropies
        ],
        name="graph_reg_fused_reg_forward",
        interpret=interpret,
    )(p.astype(jnp.float32), logpj.astype(jnp.float32),
      logpi.astype(jnp.float32), Wp.astype(jnp.float32), scalars)
    return out[0, 0]


def graph_reg_fused_pallas(
    logp: jax.Array, W: jax.Array, gamma: float, kappa: float, *,
    bi: int | None = None, bj: int | None = None, bc: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Single-pass fused Eq.-3/4 regularizer (cross + degrees + entropy).

    Returns γ Σ_ij W_ij Hc(p_i,p_j) − Σ_i (κ + γ Σ_j W_ij) H(p_i) as one
    scalar from one grid sweep.  logp: (B, C); W: (B, B).
    """
    B, C = logp.shape
    bi, bj, bc = _reg_tiles(B, C, bi, bj, bc)
    scalars = jnp.stack([gamma, kappa, gamma, 0.0]).astype(
        jnp.float32).reshape(1, 4)
    return _fused_reg_forward(logp, W, scalars, bi=bi, bj=bj, bc=bc,
                              interpret=_default_interpret(interpret))


def graph_reg_cross_pallas(
    logp: jax.Array, W: jax.Array, *,
    bi: int | None = None, bj: int | None = None, bc: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Bare cross term Σ_ij W_ij Hc(p_i,p_j) through the fused kernel
    (gc = 1, κ = ge = 0 switches the entropy/degree terms off)."""
    B, C = logp.shape
    bi, bj, bc = _reg_tiles(B, C, bi, bj, bc)
    scalars = jnp.zeros((1, 4), jnp.float32).at[0, 0].set(1.0)
    return _fused_reg_forward(logp, W, scalars, bi=bi, bj=bj, bc=bc,
                              interpret=_default_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("bi", "bj", "bc", "interpret"))
def graph_reg_pairwise_pallas(
    logp: jax.Array, W: jax.Array, *,
    bi: int = DEFAULT_BI, bj: int = DEFAULT_BJ, bc: int = DEFAULT_BC,
    interpret: bool | None = None,
) -> jax.Array:
    """Σ_ij W_ij Hc(p_i, p_j) with p = exp(logp).  logp: (B, C); W: (B, B).

    The original cross-term-only kernel, kept as the minimal reference
    Pallas path; the registry entries now route through the fused kernel.
    """
    interpret = _default_interpret(interpret)
    B, C = logp.shape
    bi, bj, bc = min(bi, B), min(bj, B), min(bc, C)
    pad_i = (-B) % bi
    pad_j = (-B) % bj
    pad_c = (-C) % bc
    # Padding: logp rows padded with 0 (p=exp(0)=1 would corrupt → pad p with
    # 0 instead by padding logp with -inf surrogate handled via exp outside).
    p = jnp.exp(logp)
    if pad_i or pad_c:
        p = jnp.pad(p, ((0, pad_i), (0, pad_c)))             # p rows -> 0
        logp_p = jnp.pad(logp, ((0, pad_j), (0, pad_c)))     # logp·0 = 0
    else:
        logp_p = logp
    Wp = jnp.pad(W, ((0, pad_i), (0, pad_j))) if (pad_i or pad_j) else W
    Bi, Bj, Cc = p.shape[0], logp_p.shape[0], p.shape[1]
    grid = (Bi // bi, Bj // bj, Cc // bc)
    out = pl.pallas_call(
        functools.partial(_graph_reg_kernel, n_c_blocks=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bi, bc), lambda i, j, c: (i, c)),
            pl.BlockSpec((bj, bc), lambda i, j, c: (j, c)),
            pl.BlockSpec((bi, bj), lambda i, j, c: (i, j)),
        ],
        out_specs=_SMEM,
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
        # VMEM scratch accumulator for the S tile.
        scratch_shapes=[pltpu.VMEM((bi, bj), jnp.float32)],
        name="graph_reg_cross",
        interpret=interpret,
    )(p.astype(jnp.float32), logp_p.astype(jnp.float32),
      Wp.astype(jnp.float32))
    return out[0, 0]


# ---------------------------------------------------------------------------
# Backward: tiled analytic VJP (no B×B intermediate outside the kernels).
# ---------------------------------------------------------------------------
def _reg_bwd_dlogp_kernel(w_ref, wt_ref, pj_ref, logpj_ref, pi_ref,
                          logpi_ref, s_ref, out_ref, a_ref, b_ref, deg_ref,
                          *, n_j: int):
    c, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init_tile():
        a_ref[...] = jnp.zeros_like(a_ref)
        b_ref[...] = jnp.zeros_like(b_ref)

    @pl.when((c == 0) & (j == 0))
    def _init_deg():
        deg_ref[...] = jnp.zeros_like(deg_ref)

    # A += W[i-blk, j-blk] @ logP[j-blk, c-blk]        (the W·logP term)
    a_ref[...] += jnp.dot(w_ref[...], logpj_ref[...],
                          precision=_F32, preferred_element_type=jnp.float32)
    # B += W[j-blk, i-blk]ᵀ @ P[j-blk, c-blk]          (the Wᵀ·P term)
    b_ref[...] += jax.lax.dot_general(
        wt_ref[...], pj_ref[...],
        (((0,), (0,)), ((), ())),
        precision=_F32, preferred_element_type=jnp.float32)

    @pl.when(c == 0)
    def _deg_chunk():
        deg_ref[...] += jnp.sum(w_ref[...], axis=1, keepdims=True)

    @pl.when(j == n_j - 1)
    def _finish():
        g, gc, kappa, ge = (s_ref[0, 0], s_ref[0, 1],
                            s_ref[0, 2], s_ref[0, 3])
        p = pi_ref[...]
        coef = kappa + ge * deg_ref[...]
        out_ref[...] = g * (-gc * (p * a_ref[...] + b_ref[...])
                            + coef * p * (logpi_ref[...] + 1.0))


def _reg_bwd_dw_kernel(pi_ref, logpj_ref, logpi_ref, s_ref, out_ref,
                       acc_ref, ent_ref, *, n_c: int):
    j, c = pl.program_id(1), pl.program_id(2)

    @pl.when(c == 0)
    def _init_tile():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((j == 0) & (c == 0))
    def _init_ent():
        ent_ref[...] = jnp.zeros_like(ent_ref)

    # S_tile += P_i(bi, bc) @ logP_j(bj, bc)^T
    acc_ref[...] += jax.lax.dot_general(
        pi_ref[...], logpj_ref[...],
        (((1,), (1,)), ((), ())),
        precision=_F32, preferred_element_type=jnp.float32)

    @pl.when(j == 0)
    def _entropy_chunk():
        ent_ref[...] += -jnp.sum(pi_ref[...] * logpi_ref[...], axis=1,
                                 keepdims=True)

    @pl.when(c == n_c - 1)
    def _finish():
        g, gc, ge = s_ref[0, 0], s_ref[0, 1], s_ref[0, 3]
        out_ref[...] = -g * (gc * acc_ref[...] + ge * ent_ref[...])


@functools.partial(jax.jit, static_argnames=("bi", "bj", "bc", "interpret"))
def _reg_bwd_dlogp(
    logp: jax.Array, W: jax.Array, scalars: jax.Array, *,
    bi: int, bj: int, bc: int, interpret: bool,
) -> jax.Array:
    """dL/dlogp tiles: grid (B/bi, C/bc, B/bj), contraction block innermost."""
    B, C = logp.shape
    pad_i, pad_j, pad_c = (-B) % bi, (-B) % bj, (-C) % bc
    p = jnp.exp(logp)
    pi, logpi = _pad2(p, pad_i, pad_c), _pad2(logp, pad_i, pad_c)
    pj, logpj = _pad2(p, pad_j, pad_c), _pad2(logp, pad_j, pad_c)
    # W is read through two views — (i, j) blocks and transposed (j, i)
    # blocks — so both axes must cover both block paddings.
    L = max(B + pad_i, B + pad_j)
    Wp = _pad2(W, L - B, L - B)
    grid = ((B + pad_i) // bi, (C + pad_c) // bc, (B + pad_j) // bj)
    out = pl.pallas_call(
        functools.partial(_reg_bwd_dlogp_kernel, n_j=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bi, bj), lambda i, c, j: (i, j)),   # W
            pl.BlockSpec((bj, bi), lambda i, c, j: (j, i)),   # W (transposed)
            pl.BlockSpec((bj, bc), lambda i, c, j: (j, c)),   # P rows j
            pl.BlockSpec((bj, bc), lambda i, c, j: (j, c)),   # logP rows j
            pl.BlockSpec((bi, bc), lambda i, c, j: (i, c)),   # P rows i
            pl.BlockSpec((bi, bc), lambda i, c, j: (i, c)),   # logP rows i
            pl.BlockSpec((1, 4), lambda i, c, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bi, bc), lambda i, c, j: (i, c)),
        out_shape=jax.ShapeDtypeStruct((B + pad_i, C + pad_c), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bi, bc), jnp.float32),   # (W·logP) tile
            pltpu.VMEM((bi, bc), jnp.float32),   # (Wᵀ·P) tile
            pltpu.VMEM((bi, 1), jnp.float32),    # row degrees
        ],
        name="graph_reg_bwd_dlogp",
        interpret=interpret,
    )(Wp.astype(jnp.float32), Wp.astype(jnp.float32),
      pj.astype(jnp.float32), logpj.astype(jnp.float32),
      pi.astype(jnp.float32), logpi.astype(jnp.float32), scalars)
    return out[:B, :C]


@functools.partial(jax.jit, static_argnames=("bi", "bj", "bc", "interpret"))
def _reg_bwd_dw(
    logp: jax.Array, scalars: jax.Array, *,
    bi: int, bj: int, bc: int, interpret: bool,
) -> jax.Array:
    """dL/dW tiles: grid (B/bi, B/bj, C/bc), class chunk innermost."""
    B, C = logp.shape
    pad_i, pad_j, pad_c = (-B) % bi, (-B) % bj, (-C) % bc
    p = jnp.exp(logp)
    pi, logpi = _pad2(p, pad_i, pad_c), _pad2(logp, pad_i, pad_c)
    logpj = _pad2(logp, pad_j, pad_c)
    grid = ((B + pad_i) // bi, (B + pad_j) // bj, (C + pad_c) // bc)
    out = pl.pallas_call(
        functools.partial(_reg_bwd_dw_kernel, n_c=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bi, bc), lambda i, j, c: (i, c)),   # P rows i
            pl.BlockSpec((bj, bc), lambda i, j, c: (j, c)),   # logP rows j
            pl.BlockSpec((bi, bc), lambda i, j, c: (i, c)),   # logP rows i
            pl.BlockSpec((1, 4), lambda i, j, c: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bi, bj), lambda i, j, c: (i, j)),
        out_shape=jax.ShapeDtypeStruct((B + pad_i, B + pad_j), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bi, bj), jnp.float32),   # S tile
            pltpu.VMEM((bi, 1), jnp.float32),    # row entropies
        ],
        name="graph_reg_bwd_dw",
        interpret=interpret,
    )(pi.astype(jnp.float32), logpj.astype(jnp.float32),
      logpi.astype(jnp.float32), scalars)
    return out[:B, :B]


def graph_reg_bwd_pallas(
    logp: jax.Array, W: jax.Array, g: jax.Array, *,
    gamma: float, kappa: float, ent_weight: float,
    bi: int | None = None, bj: int | None = None, bc: int | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Tiled analytic VJP of the fused regularizer: (dlogp, dW).

    ``gamma`` weights the cross term, ``kappa`` the uniform entropy term and
    ``ent_weight`` the degree-weighted entropy term (γ for the full
    regularizer, 0 for the bare cross term).  ``g`` is the output cotangent.
    """
    B, C = logp.shape
    bi, bj, bc = _reg_tiles(B, C, bi, bj, bc)
    interpret = _default_interpret(interpret)
    scalars = jnp.stack(
        [jnp.asarray(g, jnp.float32), jnp.float32(gamma),
         jnp.float32(kappa), jnp.float32(ent_weight)]).reshape(1, 4)
    dlogp = _reg_bwd_dlogp(logp, W, scalars, bi=bi, bj=bj, bc=bc,
                           interpret=interpret)
    dW = _reg_bwd_dw(logp, scalars, bi=bi, bj=bj, bc=bc,
                     interpret=interpret)
    return dlogp, dW


# ---------------------------------------------------------------------------
# Block-sparse variant: compacted grid over active tiles only.
#
# The §2 meta-batch W is block-structured — most bt×bt tiles are exact
# structural zeros.  A ``repro.core.metabatch.BlockLayout`` supplies
# scalar-prefetched active-tile index lists (row-major for the forward /
# dL/dlogp sweeps, column-major for the Wᵀ·P pass) so the grid is
# (n_listed_tiles, C/bc) instead of (B/bt)² × C/bc: MXU work scales with
# occupied tiles.  Accumulation order within every row strip is identical
# to the dense fused sweep (j ascending, then class chunks), so a fully
# dense occupancy mask reproduces the dense kernels bit for bit.
#
# Layout padding contract (see metabatch.BlockLayout): every empty tile
# row/column carries one valid=0 sentinel so its output block is still
# visited and written, and length padding repeats the last entry with
# valid=0 so no new strip starts and each strip finalizes exactly once.
# ---------------------------------------------------------------------------
DEFAULT_BT = 128


def _bsp_tiles(B: int, C: int, bt, bc) -> tuple[int, int]:
    """Table-selected (bt, bc) with explicit overrides; bt is never clamped
    to B — it must match the tile size the BlockLayout was built with."""
    auto = select_tiles("graph_reg_blocksparse", rows=B,
                        pinned=TileSpec(bi=bt, bc=bc))
    return (auto.bi or DEFAULT_BT), min(auto.bc or DEFAULT_BC, C)


def _bsp_check_layout(B: int, bt: int, nt: int) -> None:
    if -(-B // bt) != nt:
        raise ValueError(
            f"BlockLayout tile grid ({nt}×{nt}) does not match "
            f"ceil(B/bt) = ceil({B}/{bt}) = {-(-B // bt)}; the layout must "
            f"be built with the same tile size the kernel runs with "
            f"(pin ObjectiveConfig.tile_bt / tiles.bi consistently)")


def _bsp_fwd_kernel(rows_ref, cols_ref, valid_ref, p_ref, logpj_ref,
                    logpi_ref, w_ref, s_ref, out_ref, acc_ref, deg_ref,
                    ent_ref, *, n_t: int, n_c: int):
    t, c = pl.program_id(0), pl.program_id(1)
    row = rows_ref[t]
    first = (t == 0) | (rows_ref[jnp.maximum(t - 1, 0)] != row)
    last = (t == n_t - 1) | (rows_ref[jnp.minimum(t + 1, n_t - 1)] != row)
    live = valid_ref[t] == 1

    @pl.when((t == 0) & (c == 0))
    def _init_out():
        out_ref[0, 0] = 0.0

    @pl.when(first & (c == 0))
    def _init_row_state():
        deg_ref[...] = jnp.zeros_like(deg_ref)
        ent_ref[...] = jnp.zeros_like(ent_ref)

    @pl.when(c == 0)
    def _init_tile():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live & (c == 0))
    def _deg_chunk():
        deg_ref[...] += jnp.sum(w_ref[...], axis=1, keepdims=True)

    @pl.when(live)
    def _cross_chunk():
        # S_tile += P_i(bt, bc) @ logP_j(bt, bc)^T — skipped on sentinels.
        acc_ref[...] += jax.lax.dot_general(
            p_ref[...], logpj_ref[...],
            (((1,), (1,)), ((), ())),
            precision=_F32, preferred_element_type=jnp.float32)

    @pl.when(first)
    def _entropy_chunk():
        # H(p_i) once per row strip — NOT gated on `live`: an empty tile
        # row's sentinel still owes the κ-weighted entropy of its rows.
        ent_ref[...] += -jnp.sum(p_ref[...] * logpi_ref[...], axis=1,
                                 keepdims=True)

    gc = s_ref[0, 0]
    kappa = s_ref[0, 1]
    ge = s_ref[0, 2]

    @pl.when(live & (c == n_c - 1))
    def _finish_tile():
        out_ref[0, 0] += -gc * jnp.sum(w_ref[...] * acc_ref[...])

    @pl.when(last & (c == n_c - 1))
    def _finish_row_strip():
        out_ref[0, 0] += -jnp.sum((kappa + ge * deg_ref[...]) * ent_ref[...])


@functools.partial(jax.jit, static_argnames=("bt", "bc", "interpret"))
def _bsp_forward(
    logp: jax.Array, W: jax.Array, rows: jax.Array, cols: jax.Array,
    valid: jax.Array, scalars: jax.Array, *,
    bt: int, bc: int, interpret: bool,
) -> jax.Array:
    B, C = logp.shape
    nt = -(-B // bt)
    pad_r, pad_c = nt * bt - B, (-C) % bc
    p = _pad2(jnp.exp(logp), pad_r, pad_c).astype(jnp.float32)
    logpp = _pad2(logp, pad_r, pad_c).astype(jnp.float32)
    Wp = _pad2(W, pad_r, pad_r).astype(jnp.float32)
    T = rows.shape[0]
    n_c = (C + pad_c) // bc
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(T, n_c),
        in_specs=[
            pl.BlockSpec((bt, bc), lambda t, c, rows, cols, valid:
                         (rows[t], c)),
            pl.BlockSpec((bt, bc), lambda t, c, rows, cols, valid:
                         (cols[t], c)),
            pl.BlockSpec((bt, bc), lambda t, c, rows, cols, valid:
                         (rows[t], c)),
            pl.BlockSpec((bt, bt), lambda t, c, rows, cols, valid:
                         (rows[t], cols[t])),
            _SMEM,
        ],
        out_specs=_SMEM,
        scratch_shapes=[
            pltpu.VMEM((bt, bt), jnp.float32),   # S tile accumulator
            pltpu.VMEM((bt, 1), jnp.float32),    # row degrees
            pltpu.VMEM((bt, 1), jnp.float32),    # row entropies
        ],
    )
    out = pl.pallas_call(
        functools.partial(_bsp_fwd_kernel, n_t=T, n_c=n_c),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
        name="graph_reg_bsp_forward",
        interpret=interpret,
    )(rows, cols, valid, p, logpp, logpp, Wp, scalars)
    return out[0, 0]


def graph_reg_blocksparse_pallas(
    logp: jax.Array, W: jax.Array,
    rows: jax.Array, cols: jax.Array, valid: jax.Array,
    gamma: float, kappa: float, *, ent_weight: float | None = None,
    bt: int | None = None, bc: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Block-sparse fused Eq.-3/4 regularizer over the active tiles only.

    ``rows``/``cols``/``valid`` are the row-major active-tile list of a
    ``BlockLayout`` built with the same ``bt``.  Semantically equal to the
    dense fused kernel whenever the layout's occupancy covers every
    nonzero of W (exact by ``tile_occupancy`` construction); bit-identical
    to it on a fully dense mask.
    """
    B, C = logp.shape
    bt, bc = _bsp_tiles(B, C, bt, bc)
    ge = gamma if ent_weight is None else ent_weight
    scalars = jnp.stack([gamma, kappa, ge, 0.0]).astype(
        jnp.float32).reshape(1, 4)
    return _bsp_forward(logp, W, rows, cols, valid, scalars,
                        bt=bt, bc=bc,
                        interpret=_default_interpret(interpret))


def _bsp_bterm_kernel(crows_ref, ccols_ref, cvalid_ref, w_ref, pj_ref,
                      out_ref, b_ref, *, n_t: int):
    t = pl.program_id(1)
    col = ccols_ref[t]
    first = (t == 0) | (ccols_ref[jnp.maximum(t - 1, 0)] != col)
    last = (t == n_t - 1) | (ccols_ref[jnp.minimum(t + 1, n_t - 1)] != col)
    live = cvalid_ref[t] == 1

    @pl.when(first)
    def _init():
        b_ref[...] = jnp.zeros_like(b_ref)

    @pl.when(live)
    def _acc():
        # B += W[j-blk, i-blk]ᵀ @ P[j-blk, c-blk] — same contraction (and
        # j-ascending order per output block) as the dense dlogp kernel.
        b_ref[...] += jax.lax.dot_general(
            w_ref[...], pj_ref[...],
            (((0,), (0,)), ((), ())),
            precision=_F32, preferred_element_type=jnp.float32)

    @pl.when(last)
    def _write():
        out_ref[...] = b_ref[...]


def _bsp_dlogp_kernel(rows_ref, cols_ref, valid_ref, w_ref, logpj_ref,
                      pi_ref, logpi_ref, bterm_ref, s_ref, out_ref,
                      a_ref, deg_ref, *, n_t: int):
    t = pl.program_id(1)
    row = rows_ref[t]
    first = (t == 0) | (rows_ref[jnp.maximum(t - 1, 0)] != row)
    last = (t == n_t - 1) | (rows_ref[jnp.minimum(t + 1, n_t - 1)] != row)
    live = valid_ref[t] == 1

    @pl.when(first)
    def _init():
        a_ref[...] = jnp.zeros_like(a_ref)
        # deg is recomputed per class chunk (same adds, same j order as
        # the dense kernel's persisted scratch — bit-identical result).
        deg_ref[...] = jnp.zeros_like(deg_ref)

    @pl.when(live)
    def _acc():
        # A += W[i-blk, j-blk] @ logP[j-blk, c-blk]
        a_ref[...] += jnp.dot(w_ref[...], logpj_ref[...], precision=_F32,
                              preferred_element_type=jnp.float32)
        deg_ref[...] += jnp.sum(w_ref[...], axis=1, keepdims=True)

    @pl.when(last)
    def _finish():
        g, gc, kappa, ge = (s_ref[0, 0], s_ref[0, 1],
                            s_ref[0, 2], s_ref[0, 3])
        p = pi_ref[...]
        coef = kappa + ge * deg_ref[...]
        out_ref[...] = g * (-gc * (p * a_ref[...] + bterm_ref[...])
                            + coef * p * (logpi_ref[...] + 1.0))


def _bsp_dw_kernel(occ_ref, pi_ref, logpj_ref, logpi_ref, s_ref, out_ref,
                   acc_ref, ent_ref, *, n_t: int, n_c: int):
    i, j, c = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    live = occ_ref[i * n_t + j] == 1

    @pl.when(c == 0)
    def _init_tile():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((j == 0) & (c == 0))
    def _init_ent():
        ent_ref[...] = jnp.zeros_like(ent_ref)

    @pl.when(live)
    def _acc():
        # The MXU contraction is the only per-tile cost that matters and
        # is skipped on unoccupied tiles; the (dense) dW output block is
        # still written every tile so every gradient entry is defined.
        acc_ref[...] += jax.lax.dot_general(
            pi_ref[...], logpj_ref[...],
            (((1,), (1,)), ((), ())),
            precision=_F32, preferred_element_type=jnp.float32)

    @pl.when(j == 0)
    def _entropy_chunk():
        ent_ref[...] += -jnp.sum(pi_ref[...] * logpi_ref[...], axis=1,
                                 keepdims=True)

    @pl.when(c == n_c - 1)
    def _finish():
        g, gc, ge = s_ref[0, 0], s_ref[0, 1], s_ref[0, 3]
        val = -g * (gc * acc_ref[...] + ge * ent_ref[...])
        out_ref[...] = jnp.where(live, val, jnp.zeros_like(val))


@functools.partial(jax.jit, static_argnames=("bt", "bc", "interpret"))
def _bsp_bwd(
    logp: jax.Array, W: jax.Array,
    rows: jax.Array, cols: jax.Array, valid: jax.Array,
    crows: jax.Array, ccols: jax.Array, cvalid: jax.Array,
    occ: jax.Array, scalars: jax.Array, *,
    bt: int, bc: int, interpret: bool,
) -> tuple[jax.Array, jax.Array]:
    B, C = logp.shape
    nt = occ.shape[0]
    _bsp_check_layout(B, bt, nt)
    pad_r, pad_c = nt * bt - B, (-C) % bc
    p = _pad2(jnp.exp(logp), pad_r, pad_c).astype(jnp.float32)
    logpp = _pad2(logp, pad_r, pad_c).astype(jnp.float32)
    Wp = _pad2(W, pad_r, pad_r).astype(jnp.float32)
    P, Cc = nt * bt, C + pad_c
    T = rows.shape[0]
    n_c = Cc // bc
    # Pass 1 — column-major sweep: bterm[i-blk, c-blk] = Σ_j Wᵀ·P.
    bterm = pl.pallas_call(
        functools.partial(_bsp_bterm_kernel, n_t=T),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_c, T),
            in_specs=[
                pl.BlockSpec((bt, bt), lambda c, t, cr, cc, cv:
                             (cr[t], cc[t])),
                pl.BlockSpec((bt, bc), lambda c, t, cr, cc, cv:
                             (cr[t], c)),
            ],
            out_specs=pl.BlockSpec((bt, bc), lambda c, t, cr, cc, cv:
                                   (cc[t], c)),
            scratch_shapes=[pltpu.VMEM((bt, bc), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((P, Cc), jnp.float32),
        name="graph_reg_bsp_bwd_bterm",
        interpret=interpret,
    )(crows, ccols, cvalid, Wp, p)
    # Pass 2 — row-major sweep folds A = W·logP, degrees and bterm into
    # the dlogp tiles.
    dlogp = pl.pallas_call(
        functools.partial(_bsp_dlogp_kernel, n_t=T),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_c, T),
            in_specs=[
                pl.BlockSpec((bt, bt), lambda c, t, rows, cols, valid:
                             (rows[t], cols[t])),
                pl.BlockSpec((bt, bc), lambda c, t, rows, cols, valid:
                             (cols[t], c)),
                pl.BlockSpec((bt, bc), lambda c, t, rows, cols, valid:
                             (rows[t], c)),
                pl.BlockSpec((bt, bc), lambda c, t, rows, cols, valid:
                             (rows[t], c)),
                pl.BlockSpec((bt, bc), lambda c, t, rows, cols, valid:
                             (rows[t], c)),
                pl.BlockSpec((1, 4), lambda c, t, rows, cols, valid:
                             (0, 0)),
            ],
            out_specs=pl.BlockSpec((bt, bc), lambda c, t, rows, cols, valid:
                                   (rows[t], c)),
            scratch_shapes=[
                pltpu.VMEM((bt, bc), jnp.float32),   # (W·logP) tile
                pltpu.VMEM((bt, 1), jnp.float32),    # row degrees
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((P, Cc), jnp.float32),
        name="graph_reg_bsp_bwd_dlogp",
        interpret=interpret,
    )(rows, cols, valid, Wp, logpp, p, logpp, bterm, scalars)
    # dW — predicated-dense grid: MXU work only on occupied tiles, but
    # every (dense) output tile is written so the gradient is defined.
    dw = pl.pallas_call(
        functools.partial(_bsp_dw_kernel, n_t=nt, n_c=n_c),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nt, nt, n_c),
            in_specs=[
                pl.BlockSpec((bt, bc), lambda i, j, c, occf: (i, c)),
                pl.BlockSpec((bt, bc), lambda i, j, c, occf: (j, c)),
                pl.BlockSpec((bt, bc), lambda i, j, c, occf: (i, c)),
                pl.BlockSpec((1, 4), lambda i, j, c, occf: (0, 0)),
            ],
            out_specs=pl.BlockSpec((bt, bt), lambda i, j, c, occf: (i, j)),
            scratch_shapes=[
                pltpu.VMEM((bt, bt), jnp.float32),   # S tile
                pltpu.VMEM((bt, 1), jnp.float32),    # row entropies
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((P, P), jnp.float32),
        name="graph_reg_bsp_bwd_dw",
        interpret=interpret,
    )(occ.reshape(-1), p, logpp, logpp, scalars)
    if pad_r:
        dw = dw[:B, :B]
    if pad_c or pad_r:
        dlogp = dlogp[:B, :C]
    return dlogp, dw


def graph_reg_blocksparse_bwd_pallas(
    logp: jax.Array, W: jax.Array, g: jax.Array,
    rows: jax.Array, cols: jax.Array, valid: jax.Array,
    crows: jax.Array, ccols: jax.Array, cvalid: jax.Array,
    occ: jax.Array, *,
    gamma: float, kappa: float, ent_weight: float,
    bt: int | None = None, bc: int | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Block-sparse tiled analytic VJP: (dlogp, dW).

    Same scalar convention as ``graph_reg_bwd_pallas``; the index lists
    and occupancy mask come from the same ``BlockLayout`` as the forward.
    """
    B, C = logp.shape
    bt, bc = _bsp_tiles(B, C, bt, bc)
    scalars = jnp.stack(
        [jnp.asarray(g, jnp.float32), jnp.float32(gamma),
         jnp.float32(kappa), jnp.float32(ent_weight)]).reshape(1, 4)
    return _bsp_bwd(logp, W, rows, cols, valid, crows, ccols, cvalid, occ,
                    scalars, bt=bt, bc=bc,
                    interpret=_default_interpret(interpret))
