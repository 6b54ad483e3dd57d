"""Benchmark of graph-SSL training on TPU chips: one run of one cell.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s>
        --trace <0|1>

Runs the cell named in ``BENCHMARK.json`` in this process, on the chips
JAX finds here, and prints one JSON object as the last line of standard
output: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``, the
numbers compared with their limits (also the last lines of standard
error).  Without TPU devices, or with fewer than the cell asks for, it
exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="write the profiler trace to DIR and keep it")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    if args.seed < 0:
        print(f"bench: --seed must be >= 0, got {args.seed}", file=sys.stderr)
        return 2
    sys.path.insert(0, BENCH_DIR)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    # The program keeps its compile cache where this variable says; the
    # benchmark gives it a fixed directory inside the checkout.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    import harness
    cell = harness.load_cell(args.workload)

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: cell {cell.name} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devices)} {platform!r} device(s)", file=sys.stderr)
        return 2
    result = harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                              traced=bool(args.trace), t_start=T_START,
                              trace_keep=args.keep_trace)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
