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
    """A cell cut to a size the CPU runs in seconds, by its family."""
    return cell.family.shrink(cell)


@pytest.fixture
def tiny_cell():
    import harness

    def make(name="dnn_timit.b2048_replan"):
        return shrink(harness.load_cell(name))
    return make
