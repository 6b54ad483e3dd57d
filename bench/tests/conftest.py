"""Shared set-up of the benchmark's own tests (``pytest bench/tests``).

They run on the CPU.  The benchmark's modules live in ``bench/``, the
program in ``src/``; both go on ``sys.path`` here.
"""
from __future__ import annotations

import os
import sys
import tempfile

import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for p in (os.path.join(ROOT, "src"), BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)
# CPU programs go to a cache of their own, never to the checkout's.
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      tempfile.mkdtemp(prefix="bench_tests_cache_"))


def shrink(cell):
    """A cell cut to a size the CPU runs in seconds: its own limits and
    traffic kinds, a host-built graph, narrower layers.  Four 256-wide
    layers and a 16-step first chunk are the least at which the control
    (float8 matmul operands) drifts past the limits as it does at the
    paper's widths; at two 64-wide layers and two steps it does not."""
    cell.config.update(n_frames=4096, input_dim=32, hidden_dim=256,
                       n_hidden=4, n_classes=39, graph_construction="host")
    # The CPU multiplies float32 operands in float32, not in one bfloat16
    # pass as a TPU does at JAX's default precision.
    cell.config["dnn_operands"] = "float32"
    cell.traffic.update(batch_size=128, pad_rows=384)
    return cell


@pytest.fixture
def tiny_cell():
    import harness

    def make(name="dnn_timit.b2048_replan"):
        return shrink(harness.load_cell(name))
    return make
