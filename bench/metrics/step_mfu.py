"""``step_mfu``: percent of the chips' bf16 peak that the required FLOPs of
the window's steps reach: the DNN's forward and backward on valid rows
(3 x 2 x MACs per row) plus the regularizer's 3 x 2 n^2 C on each
worker's n valid rows, over window seconds x chips x peak.  Moves
``frames_per_s``."""
import counts


def read(rec):
    if rec.peaks is None or not rec.probe.window_steps:
        return None
    c = rec.cell.config
    dims = counts.dnn_dims(c["input_dim"], c["hidden_dim"], c["n_hidden"],
                           c["n_classes"])
    per_row = counts.dnn_train_flops_per_row(dims)
    flops = 0.0
    for valid in rec.probe.window_valid():
        for n in valid:
            flops += per_row * int(n) \
                + counts.reg_train_flops(int(n), c["n_classes"])
    peak = rec.chips * rec.peaks["bf16_flops_per_s"]
    return 100.0 * flops / (rec.window_s * peak)
