"""``device_idle_share``: percent of the window in which no operation ran
on a chip (1 - union of op intervals / window), averaged over the cell's
chips; each chip's longest gaps are in the run's ``breakdown``.  Moves
``frames_per_s``."""
import devtrace


def read(rec):
    if rec.trace is None or not rec.trace.ops:
        return None
    lo, hi = rec.trace_window
    if hi <= lo:
        return None
    shares = [1.0 - devtrace.busy_ns(ops, lo, hi) / (hi - lo)
              for ops in rec.trace.ops.values()]
    return 100.0 * sum(shares) / len(shares)
