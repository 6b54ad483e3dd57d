"""``reg_roofline``: percent of the regularizer kernels' time that the
chip's roofline would need for the same work: least time (the larger of
3 x 2 P^2 C FLOPs over peak FLOP/s and the bytes of ``counts.
reg_train_bytes`` over peak HBM bytes/s, per worker block of P padded
rows) over the summed device time of the regularizer's Pallas kernels,
found by their names in the trace.  Bound by bytes at the paper's shapes.
Moves ``frames_per_s``."""
import counts
import devtrace

#: The trace names a Pallas kernel after the jitted function that calls it
#: (``%jvp_vmap_jit__fused_reg_forward___.10 = ... custom-call(...),
#: custom_call_target="tpu_custom_call"``): these are the regularizer's,
#: dense and block-sparse, forward and backward.
KERNELS = ("_fused_reg_forward", "_reg_bwd_dlogp", "_reg_bwd_dw",
           "_bsp_forward", "_bsp_bwd")


def is_kernel(name: str) -> bool:
    return ("tpu_custom_call" in name
            and any(k in devtrace.short(name) for k in KERNELS))


def read(rec):
    if rec.trace is None or rec.peaks is None or not rec.probe.window_steps:
        return None
    lo, hi = rec.trace_window
    kernel_ns = sum(sum(devtrace.time_by_name(ops, lo, hi, is_kernel).values())
                    for ops in rec.trace.ops.values())
    if kernel_ns <= 0:
        return None
    c = rec.cell.config
    # The kernels pad the block to their 128-row tiles.
    P, C = -(-rec.pad_rows // 128) * 128, c["n_classes"]
    least, _ = counts.least_time(counts.reg_train_flops(P, C),
                                 counts.reg_train_bytes(P, C), rec.peaks)
    blocks = rec.probe.window_steps * c["n_workers"]
    return 100.0 * blocks * least / (kernel_ns / 1e9)
