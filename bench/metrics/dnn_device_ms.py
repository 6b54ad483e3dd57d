"""``dnn_device_ms``: device milliseconds per step of the family's network,
the ops whose scope path holds the scope its family module names
(``MODEL_SCOPE``: the forward, and in the backward pass its ``jvp(...)`` /
``transpose(...)``), inside the window, averaged over the cell's chips.
Moves ``frames_per_s``."""
import program_spans


def read(rec):
    trace = program_spans.of(rec)
    if trace is None or not rec.probe.window_steps:
        return None
    scope = rec.cell.family.MODEL_SCOPE
    per_chip = [program_spans.scope_ns(ops, scope, *rec.trace_window)
                for ops in trace.ops.values()]
    if not any(per_chip):
        return None
    return sum(per_chip) / len(per_chip) / 1e6 / rec.probe.window_steps
