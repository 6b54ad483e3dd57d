"""Record the small TPU trace that ``bench/tests/test_devtrace.py`` reads.

    python3 bench/tests/data/record_small_trace.py <out dir>

On one TPU chip: a jitted matmul, a tiny Pallas kernel called from a
jitted ``_fused_reg_forward`` (the trace names a kernel after the jitted
function that calls it, as it names the regularizer's) and a host span ``bench.assemble`` around a 20 ms sleep in which the chip
idles, all inside a span ``bench.window``.  The ``.xplane.pb`` it writes is
copied to ``bench/tests/data/small.xplane.pb``, with the checkout's path in
its source locations replaced by ``<checkout>`` (the same length, so the
file stays valid).
"""
from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _fused_reg_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0 + 1.0


def main(out_dir: str) -> int:
    assert jax.devices()[0].platform == "tpu", jax.devices()
    x = jnp.ones((512, 512), jnp.float32)
    mm = jax.jit(lambda a: a @ a)

    @jax.jit
    def _fused_reg_forward(a):
        return pl.pallas_call(
            _fused_reg_kernel,
            out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype))(a)

    kern = _fused_reg_forward
    jax.block_until_ready((mm(x), kern(x)))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        jax.block_until_ready(mm(x))
        with jax.profiler.TraceAnnotation("bench.assemble"):
            time.sleep(0.02)
        jax.block_until_ready(kern(x))
        jax.block_until_ready(mm(x))
    jax.profiler.stop_trace()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
