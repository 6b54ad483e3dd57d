"""Production mesh definitions (functions — importing never touches jax
device state; the dry-run sets XLA_FLAGS before calling these).

Target hardware: TPU v5e pods, 256 chips/pod.
  single-pod:  (16, 16)      axes ("data", "model")
  multi-pod:   (2, 16, 16)   axes ("pod", "data", "model")
"""
from __future__ import annotations

import jax

_AUTO = jax.sharding.AxisType.Auto
SINGLE_POD_SHAPE = (16, 16)
MULTI_POD_SHAPE = (2, 16, 16)

# TPU v5e hardware constants used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = MULTI_POD_SHAPE if multi_pod else SINGLE_POD_SHAPE
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(_AUTO,) * len(axes))


def make_debug_mesh(*, multi_pod: bool = False,
                    data: int = 2, model: int = 2) -> jax.sharding.Mesh:
    """Tiny mesh with the same axis names — used by CI-scale sharding tests."""
    if multi_pod:
        return jax.make_mesh((2, data, model), ("pod", "data", "model"),
                             axis_types=(_AUTO,) * 3)
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(_AUTO,) * 2)
