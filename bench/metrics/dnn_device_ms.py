"""``dnn_device_ms``: device milliseconds per step of the DNN's ops, those
whose scope path holds the program's ``repro.dnn`` scope (the forward, and
in the backward pass its ``jvp(...)`` / ``transpose(...)``), inside the
window, averaged over the cell's chips.  Moves ``frames_per_s``."""
import program_spans


def read(rec):
    trace = program_spans.of(rec)
    if trace is None or not rec.probe.window_steps:
        return None
    per_chip = [program_spans.scope_ns(ops, "repro.dnn", *rec.trace_window)
                for ops in trace.ops.values()]
    if not any(per_chip):
        return None
    return sum(per_chip) / len(per_chip) / 1e6 / rec.probe.window_steps
