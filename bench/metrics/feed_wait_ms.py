"""``feed_wait_ms``: host milliseconds per step in which the training loop
waited for its next placed chunk (the program's ``repro.engine.wait_chunk``
spans that start inside the window, over the window's steps): the time the
device is starved for input.  Moves ``frames_per_s``."""
import program_spans


def read(rec):
    return program_spans.per_step_ms(rec, "engine.wait_chunk")
