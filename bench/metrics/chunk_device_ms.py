"""``chunk_device_ms``: device milliseconds per step of the engine's chunk
executable (``Engine._run_chunk``, the donated scan), from the trace's
``XLA Modules`` line, averaged over the cell's chips.  Moves
``frames_per_s``."""
import devtrace


def read(rec):
    if rec.trace is None or not rec.probe.window_steps:
        return None
    lo, hi = rec.trace_window
    per_chip = [sum(devtrace.time_by_name(
        mods, lo, hi, lambda n: "_run_chunk" in n).values())
        for mods in rec.trace.modules.values()]
    if not per_chip or not any(per_chip):
        return None
    return sum(per_chip) / len(per_chip) / 1e6 / rec.probe.window_steps
