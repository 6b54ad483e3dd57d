"""``chunk_copy_ms``: host milliseconds per step spent copying each step's
batch into a host dict and stacking the chunk's steps (the program's
``repro.engine.to_host`` and ``repro.engine.stack`` spans that start inside
the window, over the window's steps).  Moves ``frames_per_s``."""
import program_spans


def read(rec):
    return program_spans.per_step_ms(rec, "engine.to_host", "engine.stack")
