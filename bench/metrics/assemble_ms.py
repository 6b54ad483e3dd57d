"""``assemble_ms``: host milliseconds per step inside the pipeline epoch
iterator's ``next()`` (``MetaBatchStream.epoch``: dense W block, pads,
stack; at an epoch's first step also the replan join), from the spans the
benchmark's wrapper records, for the steps started inside the window.
Moves ``frames_per_s``."""


def read(rec):
    p = rec.probe
    ms = p.spans.between("assemble", p.t_open, p.t_close)
    return 1e3 * sum(ms) / len(ms) if ms else None
