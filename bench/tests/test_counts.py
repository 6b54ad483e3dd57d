"""Count functions and the peaks table, against hand numbers at the
paper's widths."""
import math

import pytest

import counts
import peaks

PAPER_DIMS = counts.dnn_dims(351, 2000, 4, 39)


def test_dnn_params_at_paper_widths():
    assert counts.dnn_params(PAPER_DIMS) == 12_788_039


def test_dnn_macs_and_train_flops_per_row():
    assert counts.dnn_macs(PAPER_DIMS) == 12_780_000
    assert counts.dnn_train_flops_per_row(PAPER_DIMS) == 76_680_000


@pytest.mark.parametrize("P,C", [(5376, 39), (1216, 39), (128, 8)])
def test_regularizer_counts(P, C):
    assert counts.reg_train_flops(P, C) == 6 * P * P * C
    w = P * P * 4
    assert counts.reg_train_bytes(P, C) == 2 * w + 3 * P * C * 4


def test_w_block_bytes_at_paper_pad():
    # One f32 W block at P = 5,376 padded rows.
    assert 5376 * 5376 * counts.F32_BYTES == 115_605_504


def test_least_time_is_bound_by_bytes_at_paper_shapes():
    pk = peaks.peaks_for("TPU v5 lite")
    t, bound = counts.least_time(counts.reg_train_flops(5376, 39),
                                 counts.reg_train_bytes(5376, 39), pk)
    assert bound == "bytes"
    assert math.isclose(t, counts.reg_train_bytes(5376, 39) / 819e9)


def test_peaks_lookup_by_device_kind():
    pk = peaks.peaks_for("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12
    assert pk["hbm_bytes_per_s"] == 819e9
    assert pk["hbm_bytes"] == 16e9
    assert pk["ici_bits_per_s_per_chip"] == 1.6e12


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="TPU v9 imaginary"):
        peaks.peaks_for("TPU v9 imaginary")


def test_every_entry_names_its_source():
    for kind, entry in peaks.load_table().items():
        assert "TPU v5e" in entry["source"], kind
        assert "cloud.google.com" in entry["source"], kind
