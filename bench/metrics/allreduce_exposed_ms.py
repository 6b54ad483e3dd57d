"""``allreduce_exposed_ms``: milliseconds per step in which a collective
(the k-worker gradient all-reduce under ``sync_mesh``) ran on a chip while
no other operation did, from the trace, averaged over the cell's chips.
Moves ``frames_per_s``."""
import devtrace


def read(rec):
    if rec.trace is None or not rec.probe.window_steps:
        return None
    lo, hi = rec.trace_window
    found = [ops for ops in rec.trace.ops.values()
             if any(devtrace.is_collective(n) for n, _, _ in ops)]
    if not found:
        return None
    ns = [devtrace.exposed_collective_ns(ops, lo, hi) for ops in found]
    return sum(ns) / len(ns) / 1e6 / rec.probe.window_steps
