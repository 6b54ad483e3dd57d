"""Rehearsals without a chip: the command refuses the CPU, and
``BENCHMARK.json`` keeps to its rules."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH_DIR, ROOT

SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _run(cwd, *extra, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         SPEC["workloads"][0]["name"], "--seed", "3000000019", "--seconds",
         "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(out: str) -> bool:
    return not any(line.strip().startswith("{") for line in out.splitlines())


def test_cpu_run_exits_nonzero_names_platform_prints_no_metric():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "'cpu'" in p.stderr
    assert _no_result(p.stdout) and "frames_per_s" not in p.stdout


def test_benchmark_files_alone_exit_nonzero_without_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert _no_result(p.stdout)


def _metrics():
    return SPEC["end_to_end"] + SPEC["per_layer"]


@pytest.mark.parametrize("entry", SPEC["configs"] + SPEC["workloads"]
                         + _metrics(), ids=lambda e: e["name"])
def test_names_units_and_texts(entry):
    assert NAME.match(entry["name"])
    for key in ("config", "traffic"):
        if key in entry:
            assert NAME.match(entry[key])
    if "unit" in entry:
        assert UNIT.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")
    for key in ("why", "layer", "source"):
        if key in entry:
            assert 1 <= len(entry[key]) <= 200
            assert "\n" not in entry[key] and "\t" not in entry[key]


def test_names_are_unique():
    for group in (SPEC["configs"], SPEC["workloads"], _metrics()):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))


def test_moves_names_an_end_to_end_metric_every_listed_cell_reports():
    import harness
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    cells = [w["name"] for w in SPEC["workloads"]]
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e, m["name"]
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert harness.reports(e2e[m["moves"]], cell, SPEC["end_to_end"])


def test_every_cell_reports_setup_another_end_to_end_and_a_layer():
    import harness
    for w in SPEC["workloads"]:
        cell = harness.load_cell(w["name"])
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer


def test_every_per_layer_metric_has_a_reader():
    for m in SPEC["per_layer"]:
        assert os.path.exists(os.path.join(BENCH_DIR, "metrics",
                                           m["name"] + ".py"))


def test_configs_state_their_cuts():
    for c in SPEC["configs"]:
        assert c["file"].startswith("bench/")
        conf = json.load(open(os.path.join(ROOT, c["file"])))
        assert conf["name"] == c["name"]
        assert sorted(conf["reduced"]) == sorted(c["reduced"])


def test_bounds_and_run_seconds():
    assert 1 <= SPEC["run_seconds"] <= 51
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)
