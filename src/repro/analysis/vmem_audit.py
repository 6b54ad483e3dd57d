"""Pallas VMEM / tiling checker: static models of every kernel launch.

Each kernel family in ``repro.kernels`` is mirrored here by a *static
launch model* — the same grid, block shapes, index maps, and scratch
allocations its wrapper builds, computed from a :class:`TileSpec` and a
problem shape without touching a device.  From the model the checker

  * computes the per-grid-step VMEM footprint (input/output blocks count
    **twice** — Pallas double-buffers the HBM↔VMEM pipeline — plus
    scratch) and validates it against the backend budget (``V001``);
  * checks TPU lane/sublane alignment of every table-controlled tile dim:
    a dim used as the last (lane) axis of any block must be a multiple of
    128, any other a multiple of the f32 sublane 8 (``V002``);
  * evaluates every block's index map over the grid corners and rejects
    maps that address past the padded array bounds (``V003``);
  * proves every ``kernels/tuning.py`` row *reachable* under first-match
    (``V004``) and *modeled* (``V005``), so the hand-tuned table cannot
    silently rot.

``validate_tuning_table`` is the pass entry point; ``check_launch`` and
``vmem_footprint_bytes`` are exposed for tests and for validating custom
specs before they ever reach a TPU.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Callable, Sequence

from repro.analysis.findings import Finding
from repro.kernels.tuning import DEFAULT_TILE_TABLE, TileSpec

__all__ = [
    "Block",
    "Launch",
    "kernel_launches",
    "check_launch",
    "check_tiles",
    "vmem_footprint_bytes",
    "validate_tuning_table",
    "VMEM_BUDGET_BYTES",
]

#: Per-core VMEM (TPU ~16 MiB); the budget the whole per-step working set
#: (double-buffered blocks + scratch) must fit in.
VMEM_BUDGET_BYTES = 16 * 2 ** 20
_LANE, _SUBLANE = 128, 8       # f32 tiling: last dim 128, second-to-last 8


@dataclasses.dataclass(frozen=True)
class Block:
    """One VMEM-resident buffer of a launch: a BlockSpec or a scratch."""

    name: str
    shape: tuple[int, ...]
    kind: str                          # "in" | "out" | "scratch"
    itemsize: int = 4                  # f32/i32 kernels throughout
    #: grid index -> block coordinates (same convention as pl.BlockSpec);
    #: None for scratch buffers (not windowed over an array).
    index_map: Callable[..., tuple[int, ...]] | None = None
    #: padded logical array dims the index map windows over.
    array_shape: tuple[int, ...] | None = None
    #: grid axes along which the kernel REVISITS this (out) block and
    #: accumulates in place — the declared contract the W-pass
    #: (``race_audit``) verifies: any two grid steps mapping to the same
    #: block coordinates must differ only on these axes.
    accum_axes: tuple[int, ...] = ()
    #: "vmem", or "smem" for a whole-array scalar buffer (the forward
    #: kernels' parameters and loss accumulator), which takes no VMEM.
    memory: str = "vmem"

    @property
    def nbytes(self) -> int:
        n = 1
        for d in self.shape:
            n *= d
        return n * self.itemsize


@dataclasses.dataclass(frozen=True)
class Launch:
    """Static mirror of one ``pl.pallas_call``: grid + blocks."""

    kernel: str
    variant: str                       # e.g. "fwd", "bwd_dlogp"
    grid: tuple[int, ...]
    blocks: tuple[Block, ...]

    def footprint_bytes(self) -> int:
        """Per-grid-step VMEM working set: 2x in/out (double-buffered
        pipeline) + 1x scratch; SMEM blocks do not count."""
        total = 0
        for b in self.blocks:
            if b.memory == "vmem":
                total += b.nbytes * (1 if b.kind == "scratch" else 2)
        return total


def _ceil_to(n: int, b: int) -> int:
    return -(-n // b) * b


def _fill(kernel: str, tiles: TileSpec) -> tuple[int, int, int, int]:
    """TileSpec with the kernel's own defaults for unset dims (mirrors the
    wrapper defaults in ``repro.kernels``)."""
    defaults = {
        "graph_reg": (128, 128, 512, None),
        "graph_reg_blocksparse": (128, None, 512, None),
        "rbf": (128, 128, None, 256),
        "topk": (128, 512, None, 256),
    }[kernel]
    return tuple(t if t is not None else d
                 for t, d in zip(tiles.astuple(), defaults))


# ---------------------------------------------------------------------------
# Launch models — one per pallas_call in repro.kernels, kept in lockstep
# with the wrappers (grid construction and index maps transcribed).
# ---------------------------------------------------------------------------
def _graph_reg_launches(tiles: TileSpec, *, rows: int, classes: int
                        ) -> list[Launch]:
    bi, bj, bc, _ = _fill("graph_reg", tiles)
    bi, bj, bc = min(bi, rows), min(bj, rows), min(bc, classes)
    Bi, Bj = _ceil_to(rows, bi), _ceil_to(rows, bj)
    Cc = _ceil_to(classes, bc)
    L = max(Bi, Bj)                    # bwd W padding covers both views
    fwd_grid = (Bi // bi, Bj // bj, Cc // bc)
    fwd = Launch("graph_reg", "fwd", fwd_grid, (
        Block("p", (bi, bc), "in", index_map=lambda i, j, c: (i, c),
              array_shape=(Bi, Cc)),
        Block("logp_j", (bj, bc), "in", index_map=lambda i, j, c: (j, c),
              array_shape=(Bj, Cc)),
        Block("logp_i", (bi, bc), "in", index_map=lambda i, j, c: (i, c),
              array_shape=(Bi, Cc)),
        Block("W", (bi, bj), "in", index_map=lambda i, j, c: (i, j),
              array_shape=(Bi, Bj)),
        Block("scalars", (1, 4), "in", index_map=lambda i, j, c: (0, 0),
              array_shape=(1, 4), memory="smem"),
        Block("out", (1, 1), "out", index_map=lambda i, j, c: (0, 0),
              array_shape=(1, 1), memory="smem", accum_axes=(0, 1, 2)),
        Block("acc", (bi, bj), "scratch"),
        Block("deg", (bi, 1), "scratch"),
        Block("ent", (bi, 1), "scratch"),
    ))
    bwd_dlogp_grid = (Bi // bi, Cc // bc, Bj // bj)
    bwd_dlogp = Launch("graph_reg", "bwd_dlogp", bwd_dlogp_grid, (
        Block("W", (bi, bj), "in", index_map=lambda i, c, j: (i, j),
              array_shape=(L, L)),
        Block("Wt", (bj, bi), "in", index_map=lambda i, c, j: (j, i),
              array_shape=(L, L)),
        Block("p_j", (bj, bc), "in", index_map=lambda i, c, j: (j, c),
              array_shape=(Bj, Cc)),
        Block("logp_j", (bj, bc), "in", index_map=lambda i, c, j: (j, c),
              array_shape=(Bj, Cc)),
        Block("p_i", (bi, bc), "in", index_map=lambda i, c, j: (i, c),
              array_shape=(Bi, Cc)),
        Block("logp_i", (bi, bc), "in", index_map=lambda i, c, j: (i, c),
              array_shape=(Bi, Cc)),
        Block("scalars", (1, 4), "in", index_map=lambda i, c, j: (0, 0),
              array_shape=(1, 4)),
        Block("dlogp", (bi, bc), "out", index_map=lambda i, c, j: (i, c),
              array_shape=(Bi, Cc), accum_axes=(2,)),
        Block("a", (bi, bc), "scratch"),
        Block("b", (bi, bc), "scratch"),
        Block("deg", (bi, 1), "scratch"),
    ))
    bwd_dw = Launch("graph_reg", "bwd_dw", fwd_grid, (
        Block("p_i", (bi, bc), "in", index_map=lambda i, j, c: (i, c),
              array_shape=(Bi, Cc)),
        Block("logp_j", (bj, bc), "in", index_map=lambda i, j, c: (j, c),
              array_shape=(Bj, Cc)),
        Block("logp_i", (bi, bc), "in", index_map=lambda i, j, c: (i, c),
              array_shape=(Bi, Cc)),
        Block("scalars", (1, 4), "in", index_map=lambda i, j, c: (0, 0),
              array_shape=(1, 4)),
        Block("dW", (bi, bj), "out", index_map=lambda i, j, c: (i, j),
              array_shape=(Bi, Bj), accum_axes=(2,)),
        Block("acc", (bi, bj), "scratch"),
        Block("ent", (bi, 1), "scratch"),
    ))
    return [fwd, bwd_dlogp, bwd_dw]


def _blocksparse_launches(tiles: TileSpec, *, rows: int, classes: int
                          ) -> list[Launch]:
    """Launch models for the block-sparse regularizer (bi doubles as the
    square tile edge bt).

    The real kernels window W and the row blocks through *scalar-prefetched*
    tile-id lists (data-dependent index maps); the static stand-ins below
    clamp the grid step into the tile-id range [0, nt) — the exact bound
    ``BlockLayout`` guarantees — so the V003 corner sweep exercises both
    the first and the last addressable tile.  The tile-id lists themselves
    live in SMEM (scalar prefetch), not VMEM, and are excluded from the
    footprint.  Representative list length: a fully dense mask (T = nt²),
    the worst case for grid size and the case that must stay bit-equal to
    the dense fused kernel.
    """
    bt, _, bc, _ = _fill("graph_reg_blocksparse", tiles)
    bc = min(bc, classes)
    nt = -(-rows // bt)
    P, Cc = nt * bt, _ceil_to(classes, bc)
    n_c = Cc // bc
    T = nt * nt

    def tid(t):                        # representative in-bounds tile id
        return min(t, nt - 1)

    fwd = Launch("graph_reg_blocksparse", "fwd", (T, n_c), (
        Block("p", (bt, bc), "in", index_map=lambda t, c: (tid(t), c),
              array_shape=(P, Cc)),
        Block("logp_j", (bt, bc), "in", index_map=lambda t, c: (tid(t), c),
              array_shape=(P, Cc)),
        Block("logp_i", (bt, bc), "in", index_map=lambda t, c: (tid(t), c),
              array_shape=(P, Cc)),
        Block("W", (bt, bt), "in",
              index_map=lambda t, c: (tid(t), tid(t)), array_shape=(P, P)),
        Block("scalars", (1, 4), "in", index_map=lambda t, c: (0, 0),
              array_shape=(1, 4), memory="smem"),
        Block("out", (1, 1), "out", index_map=lambda t, c: (0, 0),
              array_shape=(1, 1), memory="smem", accum_axes=(0, 1)),
        Block("acc", (bt, bt), "scratch"),
        Block("deg", (bt, 1), "scratch"),
        Block("ent", (bt, 1), "scratch"),
    ))
    bwd_bterm = Launch("graph_reg_blocksparse", "bwd_bterm", (n_c, T), (
        Block("W", (bt, bt), "in",
              index_map=lambda c, t: (tid(t), tid(t)), array_shape=(P, P)),
        Block("p_j", (bt, bc), "in", index_map=lambda c, t: (tid(t), c),
              array_shape=(P, Cc)),
        Block("bterm", (bt, bc), "out",
              index_map=lambda c, t: (tid(t), c), array_shape=(P, Cc),
              accum_axes=(1,)),
        Block("b", (bt, bc), "scratch"),
    ))
    bwd_dlogp = Launch("graph_reg_blocksparse", "bwd_dlogp", (n_c, T), (
        Block("W", (bt, bt), "in",
              index_map=lambda c, t: (tid(t), tid(t)), array_shape=(P, P)),
        Block("logp_j", (bt, bc), "in", index_map=lambda c, t: (tid(t), c),
              array_shape=(P, Cc)),
        Block("p_i", (bt, bc), "in", index_map=lambda c, t: (tid(t), c),
              array_shape=(P, Cc)),
        Block("logp_i", (bt, bc), "in", index_map=lambda c, t: (tid(t), c),
              array_shape=(P, Cc)),
        Block("bterm", (bt, bc), "in", index_map=lambda c, t: (tid(t), c),
              array_shape=(P, Cc)),
        Block("scalars", (1, 4), "in", index_map=lambda c, t: (0, 0),
              array_shape=(1, 4)),
        Block("dlogp", (bt, bc), "out",
              index_map=lambda c, t: (tid(t), c), array_shape=(P, Cc),
              accum_axes=(1,)),
        Block("a", (bt, bc), "scratch"),
        Block("deg", (bt, 1), "scratch"),
    ))
    bwd_dw = Launch("graph_reg_blocksparse", "bwd_dw", (nt, nt, n_c), (
        Block("p_i", (bt, bc), "in", index_map=lambda i, j, c: (i, c),
              array_shape=(P, Cc)),
        Block("logp_j", (bt, bc), "in", index_map=lambda i, j, c: (j, c),
              array_shape=(P, Cc)),
        Block("logp_i", (bt, bc), "in", index_map=lambda i, j, c: (i, c),
              array_shape=(P, Cc)),
        Block("scalars", (1, 4), "in", index_map=lambda i, j, c: (0, 0),
              array_shape=(1, 4)),
        Block("dW", (bt, bt), "out", index_map=lambda i, j, c: (i, j),
              array_shape=(P, P), accum_axes=(2,)),
        Block("acc", (bt, bt), "scratch"),
        Block("ent", (bt, 1), "scratch"),
    ))
    return [fwd, bwd_bterm, bwd_dlogp, bwd_dw]


def _rbf_launches(tiles: TileSpec, *, rows: int, cols: int, feat: int
                  ) -> list[Launch]:
    bi, bj, _, bd = _fill("rbf", tiles)
    bi, bj, bd = min(bi, rows), min(bj, cols), min(bd, feat)
    Ni, Mj, Dd = _ceil_to(rows, bi), _ceil_to(cols, bj), _ceil_to(feat, bd)
    grid = (Ni // bi, Mj // bj, Dd // bd)
    return [Launch("rbf", "fwd", grid, (
        Block("x", (bi, bd), "in", index_map=lambda i, j, d: (i, d),
              array_shape=(Ni, Dd)),
        Block("y", (bj, bd), "in", index_map=lambda i, j, d: (j, d),
              array_shape=(Mj, Dd)),
        Block("nx", (bi, 1), "in", index_map=lambda i, j, d: (i, 0),
              array_shape=(Ni, 1)),
        Block("ny", (bj, 1), "in", index_map=lambda i, j, d: (j, 0),
              array_shape=(Mj, 1)),
        Block("sigma", (1, 1), "in", index_map=lambda i, j, d: (0, 0),
              array_shape=(1, 1)),
        Block("out", (bi, bj), "out", index_map=lambda i, j, d: (i, j),
              array_shape=(Ni, Mj), accum_axes=(2,)),
        Block("acc", (bi, bj), "scratch"),
    ))]


def _topk_launches(tiles: TileSpec, *, rows: int, cols: int, feat: int,
                   k: int) -> list[Launch]:
    bi, bj, _, bd = _fill("topk", tiles)
    bi, bj, bd = min(bi, rows), min(bj, cols), min(bd, feat)
    Ni, Mj, Dd = _ceil_to(rows, bi), _ceil_to(cols, bj), _ceil_to(feat, bd)
    grid = (Ni // bi, Mj // bj, Dd // bd)
    return [Launch("topk", "fwd", grid, (
        Block("x", (bi, bd), "in", index_map=lambda i, j, d: (i, d),
              array_shape=(Ni, Dd)),
        Block("y", (bj, bd), "in", index_map=lambda i, j, d: (j, d),
              array_shape=(Mj, Dd)),
        Block("nx", (bi, 1), "in", index_map=lambda i, j, d: (i, 0),
              array_shape=(Ni, 1)),
        Block("ny", (bj, 1), "in", index_map=lambda i, j, d: (j, 0),
              array_shape=(Mj, 1)),
        Block("out_d2", (bi, k), "out", index_map=lambda i, j, d: (i, 0),
              array_shape=(Ni, k), accum_axes=(1, 2)),
        Block("out_idx", (bi, k), "out", index_map=lambda i, j, d: (i, 0),
              array_shape=(Ni, k), accum_axes=(1, 2)),
        Block("acc", (bi, bj), "scratch"),
        # The running top-k state and the (bi, k+bj) merge candidate set
        # the kernel concatenates per chunk live in VMEM too.
        Block("best_d2", (bi, k), "scratch"),
        Block("best_idx", (bi, k), "scratch"),
        Block("merge_cand", (2 * bi, k + bj), "scratch"),
    ))]


#: kernel name -> (model fn, which tile dims feed a lane (last) axis, and
#: which only ever feed sublane axes).  Lane dims must be 128-aligned on
#: TPU; sublane dims 8-aligned (f32).
_MODELS: dict[str, dict] = {
    "graph_reg": {"launches": _graph_reg_launches,
                  # bi is a lane dim too: the bwd transposed-W view (bj, bi).
                  "lane": ("bi", "bj", "bc"), "sublane": ()},
    # The square tile edge bt rides bi; it is the last axis of every
    # (bt, bt) W/dW block, so it is lane-constrained like bc.
    "graph_reg_blocksparse": {"launches": _blocksparse_launches,
                              "lane": ("bi", "bc"), "sublane": ()},
    "rbf": {"launches": _rbf_launches,
            "lane": ("bj", "bd"), "sublane": ("bi",)},
    "topk": {"launches": _topk_launches,
             "lane": ("bj", "bd"), "sublane": ("bi",)},
}

#: Representative problem shape per kernel when a table row is unbounded
#: (max_rows=None): large enough to exercise full-size tiles.
_DEFAULT_SHAPES = {
    "graph_reg": dict(rows=4096, classes=39),
    "graph_reg_blocksparse": dict(rows=4096, classes=39),
    "rbf": dict(rows=4096, cols=4096, feat=351),
    "topk": dict(rows=4096, cols=4096, feat=351, k=16),
}


def kernel_launches(kernel: str, tiles: TileSpec, **shape) -> list[Launch]:
    """The static launch models for ``kernel`` at ``tiles`` and ``shape``."""
    if kernel not in _MODELS:
        raise KeyError(f"no VMEM model for kernel {kernel!r}; "
                       f"known: {sorted(_MODELS)}")
    kw = dict(_DEFAULT_SHAPES[kernel])
    kw.update(shape)
    return _MODELS[kernel]["launches"](tiles, **kw)


def vmem_footprint_bytes(kernel: str, tiles: TileSpec, **shape) -> int:
    """Worst per-grid-step VMEM working set over the kernel's launches."""
    return max(ln.footprint_bytes()
               for ln in kernel_launches(kernel, tiles, **shape))


def check_launch(launch: Launch, *, where: str,
                 budget_bytes: int = VMEM_BUDGET_BYTES) -> list[Finding]:
    """V001 + V003 for one launch: budget and index-map bounds.

    Index maps are evaluated at every grid *corner* — the maps Pallas
    kernels use are affine in the grid indices, so an out-of-bounds block
    shows up at a corner if it shows up anywhere.
    """
    findings = []
    fp = launch.footprint_bytes()
    if fp > budget_bytes:
        findings.append(Finding(
            "vmem", "V001", where,
            f"{launch.kernel}/{launch.variant}: per-grid-step VMEM "
            f"footprint {fp / 2**20:.2f} MiB exceeds the "
            f"{budget_bytes / 2**20:.0f} MiB budget "
            f"(grid={launch.grid})",
            detail=launch.variant))
    corners = itertools.product(*[
        sorted({0, g - 1}) for g in launch.grid])
    for corner in corners:
        for b in launch.blocks:
            if b.index_map is None or b.array_shape is None:
                continue
            coords = b.index_map(*corner)
            for axis, (c, blk, dim) in enumerate(
                    zip(coords, b.shape, b.array_shape)):
                start = c * blk
                if start < 0 or start + blk > dim:
                    findings.append(Finding(
                        "vmem", "V003", where,
                        f"{launch.kernel}/{launch.variant}: block "
                        f"{b.name!r} axis {axis} addresses "
                        f"[{start}, {start + blk}) outside padded dim "
                        f"{dim} at grid index {corner}",
                        detail=f"{launch.variant}:{b.name}:{axis}"))
                    break
    return findings


def check_tiles(kernel: str, tiles: TileSpec, *, where: str,
                backend: str | None = "tpu",
                budget_bytes: int = VMEM_BUDGET_BYTES,
                **shape) -> list[Finding]:
    """Full static validation of one (kernel, tiles) combination:
    alignment (V002, TPU-reachable rows only), VMEM budget (V001) and
    index-map bounds (V003)."""
    model = _MODELS.get(kernel)
    if model is None:
        return [Finding("vmem", "V005", where,
                        f"kernel {kernel!r} has no VMEM model — add one to "
                        "repro.analysis.vmem_audit", detail=kernel)]
    findings = []
    if backend in (None, "tpu"):       # row may run on a TPU
        filled = dict(zip(("bi", "bj", "bc", "bd"), _fill(kernel, tiles)))
        for dim in model["lane"]:
            v = filled[dim]
            if v is not None and v % _LANE:
                findings.append(Finding(
                    "vmem", "V002", where,
                    f"{kernel}: tile dim {dim}={v} feeds a lane (last) "
                    f"axis and must be a multiple of {_LANE} on TPU",
                    detail=f"{dim}"))
        for dim in model["sublane"]:
            v = filled[dim]
            if v is not None and v % _SUBLANE:
                findings.append(Finding(
                    "vmem", "V002", where,
                    f"{kernel}: tile dim {dim}={v} feeds a sublane axis "
                    f"and must be a multiple of {_SUBLANE} on TPU (f32)",
                    detail=f"{dim}"))
    for launch in kernel_launches(kernel, tiles, **shape):
        findings.extend(check_launch(launch, where=where,
                                     budget_bytes=budget_bytes))
    return findings


def _row_shadowed(table: Sequence, idx: int) -> int | None:
    """Index of an earlier row that matches every (backend, rows) the row
    at ``idx`` matches — making it unreachable under first-match."""
    kern, be, max_rows, _ = table[idx]
    for early in range(idx):
        k1, be1, mr1, _ = table[early]
        if k1 != kern:
            continue
        be_covers = be1 is None or (be is not None and be1 == be)
        rows_covers = mr1 is None or (max_rows is not None
                                      and max_rows <= mr1)
        if be_covers and rows_covers:
            return early
    return None


def validate_tuning_table(table=DEFAULT_TILE_TABLE, *,
                          budget_bytes: int = VMEM_BUDGET_BYTES
                          ) -> tuple[list[Finding], dict]:
    """The VMEM pass entry point: every table row modeled, reachable,
    aligned, in budget, and in bounds."""
    findings: list[Finding] = []
    worst: dict[str, int] = {}
    for idx, (kernel, backend, max_rows, tiles) in enumerate(table):
        where = f"tuning[{idx}]:{kernel}"
        shadow = _row_shadowed(table, idx)
        if shadow is not None:
            findings.append(Finding(
                "vmem", "V004", where,
                f"row {idx} ({kernel}, backend={backend}, "
                f"max_rows={max_rows}) is shadowed by row {shadow} and can "
                "never match (first-match table)",
                detail=f"shadowed-by-{shadow}"))
        shape = {}
        if max_rows is not None:
            shape["rows"] = max_rows
            if kernel in ("rbf", "topk"):
                shape["cols"] = max_rows
        row_findings = check_tiles(kernel, tiles, where=where,
                                   backend=backend,
                                   budget_bytes=budget_bytes, **shape)
        findings.extend(row_findings)
        if not any(f.rule == "V005" for f in row_findings):
            fp = vmem_footprint_bytes(kernel, tiles, **shape)
            worst[kernel] = max(worst.get(kernel, 0), fp)
    metrics = {
        "rows_checked": len(table),
        "budget_bytes": budget_bytes,
        "worst_footprint_bytes": worst,
    }
    return findings, metrics
