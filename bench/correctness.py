"""The comparison that decides ``correct`` for a training cell.

Both sides are the first scan chunk of training from the seed: the
program's per-step losses, its per-step regularizer value and its state
after the chunk, against a plain reference that follows the same steps on
the same rows.  The compiled chunk exposes its state only at its end, so
the gradient is read from the AdaGrad accumulator there: ``sqrt(sum(G))``
of a leaf is the root of the summed squared gradients of the chunk's
steps, as the optimizer got them.

Numbers of the step (``compare``), each a worst case:

* ``loss_gap``: over the chunk's steps, ``|loss - ref| / |ref|``;
  ``loss_gap_step1`` the same at the first step alone;
* ``reg_gap``: over the chunk's steps, the gap of the summed regularizer
  (``loss/graph``), over the reference's value at that step or the median
  step's, whichever is larger; ``reg_gap_step1`` at the first step;
* ``grad_gap``: over leaves, the gap between the program's and the
  reference's accumulated gradient norm, over the reference's norm of that
  leaf or of the median leaf, whichever is larger;
* ``update_gap``: the same for the norm of the parameters' change over the
  chunk.

A leaf whose reference gradient norm is under a thousandth of the median
leaf's moves by round-off alone under AdaGrad and is left out of both leaf
numbers.

Numbers of the regularizer kernels (``kernel_numbers``): the program's
regularizer entry, called at the timed shapes on each block of the chunk
with log-probabilities the reference computed, against the reference's
regularizer of the same inputs:

* ``kernel_reg_gap``: over blocks, ``|greg - ref| / |ref|``;
* ``kernel_grad_gap``: over blocks, the norm of the gap between the two
  gradients by the log-probabilities, over the reference gradient's norm.

Numbers of the batches the chunk trained on (``batch_numbers``), against
the corpus and a k-NN graph the reference built from its features:

* ``rows_gap``: rows that are not what they claim: a valid row whose
  features are no corpus row or whose label or label mask differs from
  that row's, and a padding row that carries a weight or a label;
* ``graph_gap``: over every worker's block, the summed absolute gap
  between the batch's affinity block and the reference graph's block of the
  same corpus rows, over the reference block's summed weight.
"""
from __future__ import annotations

import numpy as np

#: Leaves whose reference gradient norm is below this share of the median
#: leaf's are not compared.
MOVING_LEAF_SHARE = 1e-3


def _norms(leaves) -> np.ndarray:
    return np.array([np.linalg.norm(np.asarray(a, np.float64))
                     for a in leaves])


def _flat(pairs) -> list:
    return [a for wb in pairs for a in wb]


def worst_leaf_gap(prog: np.ndarray, ref: np.ndarray,
                   counted: np.ndarray) -> float:
    scale = np.maximum(ref, np.median(ref))
    return float(np.max(np.abs(prog - ref)[counted] / scale[counted]))


def compare(program: dict, reference: dict, detail: bool = False) -> dict:
    """``{loss_gap, loss_gap_step1, grad_gap, update_gap, leaves_compared}``
    (with ``detail`` also each step's loss gap); each side is ``{losses,
    params0, params, accum}`` with params as ``(w, b)`` lists."""
    lp = np.asarray(program["losses"], np.float64)
    lr = np.asarray(reference["losses"], np.float64)
    if lp.shape != lr.shape:
        raise ValueError(f"loss series differ in length: {lp.shape} vs "
                         f"{lr.shape}")
    with np.errstate(invalid="ignore", divide="ignore"):
        step_gaps = np.abs(lp - lr) / np.abs(lr)

    def grad(side):
        return _norms([np.sqrt(np.maximum(np.asarray(a, np.float64), 0))
                       for a in _flat(side["accum"])])

    g_prog, g_ref = grad(program), grad(reference)
    counted = g_ref >= MOVING_LEAF_SHARE * np.median(g_ref)

    def change(side):
        return _norms([np.asarray(p, np.float64) - np.asarray(q, np.float64)
                       for p, q in zip(_flat(side["params"]),
                                       _flat(side["params0"]))])

    gp = np.asarray(program["greg"], np.float64)
    gr = np.asarray(reference["greg"], np.float64)
    with np.errstate(invalid="ignore", divide="ignore"):
        reg_gaps = np.abs(gp - gr) / np.maximum(np.abs(gr),
                                                np.median(np.abs(gr)))
    out = {"loss_gap": float(np.max(step_gaps)),
           "loss_gap_step1": float(step_gaps[0]),
           "reg_gap": float(np.max(reg_gaps)),
           "reg_gap_step1": float(reg_gaps[0]),
           "grad_gap": worst_leaf_gap(g_prog, g_ref, counted),
           "update_gap": worst_leaf_gap(change(program), change(reference),
                                        counted),
           "leaves_compared": int(counted.sum())}
    # A non-finite reading is a failure, never a pass by comparison.
    out = {k: (v if np.isfinite(v) else float("inf")) for k, v in out.items()}
    if detail:
        out["loss_gap_steps"] = [float(g) for g in step_gaps]
        out["reg_gap_steps"] = [float(g) for g in reg_gaps]
    return out


def kernel_numbers(pairs) -> dict:
    """``{kernel_reg_gap, kernel_grad_gap}`` over ``(program, reference)``
    pairs of ``(value, gradient)``."""
    reg = grad = 0.0
    for (vp, gp), (vr, gr) in pairs:
        vp, vr = float(vp), float(vr)
        gp, gr = np.asarray(gp, np.float64), np.asarray(gr, np.float64)
        reg = max(reg, abs(vp - vr) / abs(vr))
        grad = max(grad, float(np.linalg.norm(gp - gr) / np.linalg.norm(gr)))
    out = {"kernel_reg_gap": reg, "kernel_grad_gap": grad}
    return {k: (v if np.isfinite(v) else float("inf")) for k, v in out.items()}


def batch_numbers(chunk: dict, X: np.ndarray, y: np.ndarray,
                  label_mask: np.ndarray, W_ref) -> dict:
    """``{rows_gap, graph_gap}`` of the ``(S, k, P, ...)`` host ``chunk``
    against the corpus (``X``, ``y``, ``label_mask``) and the reference's
    sparse affinity matrix ``W_ref``."""
    index = {row.tobytes(): i for i, row in enumerate(
        np.ascontiguousarray(X, np.float32))}
    bad = 0
    diff = weight = 0.0
    S, k = chunk["x"].shape[:2]
    for t in range(S):
        for w in range(k):
            x = np.ascontiguousarray(chunk["x"][t, w], np.float32)
            valid = np.asarray(chunk["valid"][t, w], bool)
            lab = np.asarray(chunk["label_mask"][t, w]) != 0
            Wb = np.asarray(chunk["W"][t, w])
            pad = np.flatnonzero(~valid)
            bad += int(np.count_nonzero(lab[pad])
                       + np.count_nonzero(Wb[pad].any(axis=1)
                                          | Wb[:, pad].any(axis=0)))
            rows = np.flatnonzero(valid)
            idx = np.array([index.get(x[r].tobytes(), -1) for r in rows])
            found = idx >= 0
            rows, idx = rows[found], idx[found]
            bad += int(np.count_nonzero(~found)
                       + np.count_nonzero(np.asarray(chunk["y"][t, w])[rows]
                                          != y[idx])
                       + np.count_nonzero(lab[rows] != label_mask[idx]))
            ref = W_ref[idx][:, idx].toarray()
            diff += float(np.abs(Wb[np.ix_(rows, rows)].astype(np.float64)
                                 - ref).sum())
            weight += float(ref.sum(dtype=np.float64))
    gap = diff / weight if weight > 0 else float("inf")
    return {"rows_gap": bad, "graph_gap": gap}


def judge(numbers: dict, limits: dict) -> tuple[bool, list[str]]:
    """``(all within, lines)``: one line per number, with its limit."""
    lines, ok = [], True
    for name, limit in limits.items():
        val = numbers[name]
        within = bool(val <= limit)
        ok &= within
        lines.append(f"check {name} {val:.6g} limit {limit:.6g} "
                     f"{'ok' if within else 'FAIL'}")
    return ok, lines
