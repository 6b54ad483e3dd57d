"""Readings that the correctness limits of a cell are set from.

    python3 bench/calibrate.py --workload <name> --seeds 1,2,... \\
        [--control-seeds 3] [--out FILE]

On the chip, in one process: for every seed, the cell's program is driven
from the seed through its first scan chunk (the steps ``bench/run.py``
checks), and its numbers against the reference are printed as one JSON
line.  For the first ``--control-seeds`` seeds it also prints the numbers
of each control (``CONTROLS`` of ``references/<name>.py``: the reference
below the stated precision, in the program's place, in the step and in
the regularizer kernels), of each fault the cell can have (planted in the
reference put in the program's place) and of a batch whose affinity block pairs the neighbour rows with the
wrong columns (``batch.wrong_neighbours``).  A summary line gives, per
number, the largest sound reading and the smallest reading of each
control and fault.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
STEP = ("loss_gap", "loss_gap_step1", "reg_gap", "reg_gap_step1",
        "grad_gap", "update_gap")
KERNEL = ("kernel_reg_gap", "kernel_grad_gap")
BATCH = ("rows_gap", "graph_gap")


def shift_neighbours(W, n: int):
    """The ``n`` valid rows of an affinity block with the second half of
    rows and columns (the sampled neighbour meta-batch) shifted by one."""
    import numpy as np
    h = np.arange(n)
    h[n // 2:] = np.roll(h[n // 2:], 1)
    W = np.array(W)
    W[:n, :n] = W[np.ix_(h, h)]
    return W


def wrong_neighbours(chunk: dict) -> dict:
    """``chunk`` with ``shift_neighbours`` applied to every block."""
    import numpy as np
    W = np.array(chunk["W"])
    for t in range(W.shape[0]):
        for w in range(W.shape[1]):
            n = int(np.asarray(chunk["valid"][t, w]).sum())
            W[t, w] = shift_neighbours(W[t, w], n)
    return dict(chunk, W=W)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, BENCH_DIR)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    import jax

    import harness
    cell = harness.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"calibrate: {cell.name} needs {cell.chips} TPU chip(s); "
              f"found {len(devices)} {devices[0].platform!r}",
              file=sys.stderr)
        return 2
    harness.setup_jax()
    ref = harness.load_module("references", cell.config["reference"])
    faults = [f for f in ref.FAULTS
              if f != "no_exchange" or cell.config["n_workers"] > 1]
    rows = []
    out = open(args.out, "w") if args.out else None
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        probe = harness.Probe(seconds=0.0, prefetch=0, trace_dir=None,
                              first_only=True)
        info = harness.drive(cell, seed, probe)
        first_chunk, first = probe.first_chunk, probe.first
        probe.first_chunk = probe.first = None
        del probe
        gc.collect()
        row = {"seed": seed, "pad": info["pad"],
               "program": harness.reference_numbers(
                   cell, seed, first_chunk, first, detail=True)}
        row["program"].update(harness.kernel_check(cell, seed, first_chunk))
        row["program"].update(harness.batch_check(cell, info, first_chunk))
        if i < args.control_seeds:
            for name, kw in ref.CONTROLS.items():
                row[name] = harness.reference_numbers(
                    cell, seed, first_chunk, first, detail=True,
                    **kw["step"])
                row[name].update(harness.kernel_check(
                    cell, seed, first_chunk, **kw["kernel"]))
            for fault in faults:
                row[fault] = harness.reference_numbers(
                    cell, seed, first_chunk, first, detail=True,
                    fault=fault)
            row["batch.wrong_neighbours"] = harness.batch_check(
                cell, info, wrong_neighbours(first_chunk))
        del first_chunk, first, info
        gc.collect()
        rows.append(row)
        line = json.dumps(row)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
    numbers = STEP + KERNEL + BATCH + ("replans_kept",)
    summary = {"workload": cell.name, "seeds": len(rows),
               "program_max": {k: max(r["program"][k] for r in rows)
                               for k in numbers}}
    for name in {k for r in rows for k in r} - {"seed", "pad", "program"}:
        got = [r[name] for r in rows if name in r]
        summary[name + "_min"] = {k: min(g[k] for g in got) for k in got[0]
                                  if k in numbers}
    line = json.dumps({"summary": summary})
    print(line, flush=True)
    if out:
        out.write(line + "\n")
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
