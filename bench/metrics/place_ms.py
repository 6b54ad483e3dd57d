"""``place_ms``: host milliseconds per step spent putting chunks on the
device (the program's ``repro.engine.place`` spans that start inside the
window, over the window's steps: the enqueue of the host-to-device copy).
Moves ``frames_per_s``."""
import program_spans


def read(rec):
    return program_spans.per_step_ms(rec, "engine.place")
