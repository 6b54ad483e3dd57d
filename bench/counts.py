"""Operations and bytes that the paper's graph-SSL step requires, from shapes.

These are counts of the work the mathematics asks for, whatever implements
it: recomputation, padding and extra precision passes do not count.

* The DNN (351 -> 4x2000 -> 39 in the paper) does ``sum(d_i * d_{i+1})``
  multiply-adds per row forward; forward plus backward is three times the
  forward's ``2 * MACs`` FLOPs.
* The Eq.-3/4 regularizer over a block of ``n`` rows and ``C`` classes is
  one ``n x n x C`` contraction forward (``S = P logP^T``) and two backward
  (``W logP`` and ``W^T P``): ``3 * 2 n^2 C`` FLOPs.  The gradient with
  respect to W is not required, since W is data.
* Its bytes: the forward reads W and logp once; the backward reads W and
  logp once and writes dlogp once; at the dtype the kernels receive (f32).
"""
from __future__ import annotations

F32_BYTES = 4


def dnn_dims(input_dim: int, hidden_dim: int, n_hidden: int,
             n_classes: int) -> list[int]:
    return [input_dim] + [hidden_dim] * n_hidden + [n_classes]


def dnn_macs(dims: list[int]) -> int:
    """Multiply-adds of one row's forward pass."""
    return sum(a * b for a, b in zip(dims[:-1], dims[1:]))


def dnn_params(dims: list[int]) -> int:
    """Weights plus biases."""
    return dnn_macs(dims) + sum(dims[1:])


def dnn_train_flops_per_row(dims: list[int]) -> int:
    """Forward plus backward FLOPs of one row: 3 x 2 x MACs."""
    return 3 * 2 * dnn_macs(dims)


def reg_train_flops(n: int, n_classes: int) -> int:
    """Forward plus backward FLOPs of the regularizer over ``n`` rows."""
    return 3 * 2 * n * n * n_classes


def reg_train_bytes(P: int, n_classes: int, itemsize: int = F32_BYTES) -> int:
    """Least HBM bytes of the regularizer's forward and backward at the
    padded block size ``P``: W and logp read by each, dlogp written."""
    w = P * P * itemsize
    logp = P * n_classes * itemsize
    return (w + logp) + (w + logp + logp)


def least_time(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """``(seconds, bound)``: the larger of FLOPs over peak FLOP/s and bytes
    over peak bytes/s, and which of the two decides."""
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")
