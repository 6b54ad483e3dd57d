"""Model families: every configuration has its family module, the harness
and the readers know no family, and a new family joins as new files."""
import json
import os
import re
import types

import pytest

import harness
from conftest import BENCH_DIR, ROOT

SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
ATTRS = ("experiment_config", "warm_eval", "EVAL", "host_state",
         "reference_numbers", "kernel_check", "batch_check", "step_flops",
         "MODEL_SCOPE", "shrink")
#: Words that only a family module may use.
FAMILY_WORDS = ("evaluate_dnn", "hidden_dim", "n_hidden", "input_dim",
                '"layers"', "repro.dnn")
SHARED = ["harness.py", "run.py", "calibrate.py"] + sorted(
    os.path.join("metrics", f) for f in os.listdir(
        os.path.join(BENCH_DIR, "metrics")) if f.endswith(".py"))


@pytest.mark.parametrize("conf", SPEC["configs"], ids=lambda c: c["name"])
def test_every_configuration_has_a_whole_family(conf):
    config = json.load(open(os.path.join(ROOT, conf["file"])))
    family = harness.load_module("families", config["reference"])
    for attr in ATTRS:
        assert hasattr(family, attr), (config["reference"], attr)
    assert os.path.exists(os.path.join(BENCH_DIR, "references",
                                       config["reference"] + ".py"))


@pytest.mark.parametrize("path", SHARED)
def test_shared_code_names_no_family(path):
    text = open(os.path.join(BENCH_DIR, path)).read()
    found = [w for w in FAMILY_WORDS if w in text]
    assert not found, (path, found)


STUB_FAMILY = '''
EVAL = ("json", "dumps")
MODEL_SCOPE = "stub.seq"


def step_flops(config, valid_rows):
    return sum(config["flops_per_row"] * int(n) for n in valid_rows)
'''


def _stub_root(tmp_path, reference="stub_seq"):
    """A ``bench/``-shaped root with one configuration of a new family,
    one traffic mix and one cell."""
    files = {
        "BENCHMARK.json": {
            "configs": [{"name": "stub_seq", "source": "https://example.org",
                         "file": "bench/configs/stub_seq.json",
                         "reduced": [], "why": "a new family"}],
            "workloads": [{"name": "stub_seq.short", "config": "stub_seq",
                           "traffic": "short", "chips": 1, "why": "stub"}],
            "end_to_end": SPEC["end_to_end"], "per_layer": []},
        "bench/configs/stub_seq.json": {
            "name": "stub_seq", "reference": reference, "n_classes": 7,
            "n_workers": 1, "base_lr": 0.1, "flops_per_row": 3},
        "bench/traffic/short.json": {
            "pad_rows": 64, "prefetch": 1, "repartition_every_n_epochs": 0},
        "bench/limits/stub_seq.short.json": {"limits": {"loss_gap": 0.5}},
    }
    for rel, obj in files.items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(obj))
    (tmp_path / "bench" / "families").mkdir()
    (tmp_path / "bench" / "families" / "stub_seq.py").write_text(STUB_FAMILY)
    return str(tmp_path)


def test_a_new_family_joins_as_new_files(tmp_path):
    cell = harness.load_cell("stub_seq.short", root=_stub_root(tmp_path))
    assert cell.family.MODEL_SCOPE == "stub.seq"
    assert cell.config["flops_per_row"] == 3
    assert cell.traffic["pad_rows"] == 64
    assert cell.limits == {"limits": {"loss_gap": 0.5}}
    assert {m["name"] for m in cell.end_to_end} == {"frames_per_s", "setup_s"}
    # The readers of the family's FLOPs and scope take them from the cell.
    rec = types.SimpleNamespace(
        cell=cell, chips=1, window_s=2.0, peaks={"bf16_flops_per_s": 100.0},
        probe=types.SimpleNamespace(window_steps=2, window_valid=lambda: [
            [10], [20]]))
    mfu = harness.load_module("metrics", "step_mfu").read(rec)
    assert mfu == pytest.approx(100.0 * 3 * 30 / (2.0 * 100.0))


def test_a_configuration_without_its_family_module_is_an_error(tmp_path):
    root = _stub_root(tmp_path, reference="nowhere")
    with pytest.raises(FileNotFoundError,
                       match=re.escape("'stub_seq'") + ".*'nowhere'"):
        harness.load_cell("stub_seq.short", root=root)


@pytest.mark.parametrize("valid", [[5376], [4100], [1201, 1344, 999, 1376]])
def test_dnn_step_flops_at_the_paper_dims(valid):
    config = json.load(open(os.path.join(ROOT, "bench/configs/dnn_timit.json")))
    family = harness.load_module("families", "dnn_ssl")
    want = sum(76_680_000 * n + 3 * 2 * n * n * 39 for n in valid)
    assert family.step_flops(config, valid) == want


def test_step_mfu_reads_as_the_sum_over_blocks_did():
    """The reader's number equals the float sum, block by block, that it
    made before the family named the FLOPs, to the last bit."""
    config = json.load(open(os.path.join(
        ROOT, "bench/configs/dnn_timit_k4.json")))
    steps = [[1201 + s, 1344, 999 - s, 1376] for s in range(400)]
    rec = types.SimpleNamespace(
        cell=types.SimpleNamespace(
            config=config, family=harness.load_module("families", "dnn_ssl")),
        chips=4, window_s=30.7, peaks={"bf16_flops_per_s": 197e12},
        probe=types.SimpleNamespace(window_steps=len(steps),
                                    window_valid=lambda: steps))
    flops = 0.0
    for valid in steps:
        for n in valid:
            flops += 76_680_000 * n + 3 * 2 * n * n * 39
    want = 100.0 * flops / (30.7 * 4 * 197e12)
    assert harness.load_module("metrics", "step_mfu").read(rec) == want
