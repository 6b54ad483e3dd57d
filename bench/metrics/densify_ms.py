"""``densify_ms``: host milliseconds per step spent making the dense,
padded affinity blocks W of the step's k workers (the program's
``repro.pipeline.densify`` spans that start inside the window, summed,
over the window's steps).  Moves ``frames_per_s``."""
import program_spans


def read(rec):
    return program_spans.per_step_ms(rec, "pipeline.densify")
