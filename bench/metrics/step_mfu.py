"""``step_mfu``: percent of the chips' bf16 peak that the required FLOPs of
the window's steps reach: the family's ``step_flops`` of each step's valid
rows per worker block (for ``dnn_ssl``, the DNN's 3 x 2 x MACs per row
plus the regularizer's 3 x 2 n^2 C per block), over window seconds x chips
x peak.  Moves ``frames_per_s``."""


def read(rec):
    if rec.peaks is None or not rec.probe.window_steps:
        return None
    step_flops = rec.cell.family.step_flops
    flops = sum(step_flops(rec.cell.config, valid)
                for valid in rec.probe.window_valid())
    peak = rec.chips * rec.peaks["bf16_flops_per_s"]
    return 100.0 * flops / (rec.window_s * peak)
