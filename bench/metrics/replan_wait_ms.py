"""``replan_wait_ms``: host milliseconds per replan join in which the feed
blocked on the background re-partitioning (the program's
``repro.replan.join`` spans that start inside the window, averaged; none
when no join starts there).  Moves ``frames_per_s``."""
import program_spans


def read(rec):
    trace = program_spans.of(rec)
    if trace is None:
        return None
    joins = program_spans.in_window(trace.spans, ("replan.join",),
                                    *rec.trace_window)
    if not joins:
        return None
    return sum(sp.end - sp.start for sp in joins) / len(joins) / 1e6
