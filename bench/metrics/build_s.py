"""``build_s``: host seconds of ``Experiment.build()`` (corpus, device k-NN
graph, initial partition plan, stream).  Moves ``setup_s``."""


def read(rec):
    return rec.build_s
