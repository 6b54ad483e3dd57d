"""``chip_smoke.py``'s logic on the CPU, at a tiny size.

The phases run here with the Pallas kernels in interpret mode and the
platform check left out (it is ``main``'s first step); on a CPU ``main``
itself must refuse to run.
"""
import dataclasses
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def tiny_config(**kw):
    """Paper widths cut to a CPU-sized corpus and DNN; more labels and a
    larger step so accuracy clears chance within an epoch."""
    cfg = chip_smoke.paper_config(n=1200, hidden_dim=64, n_hidden=2,
                                  batch_size=128, pairwise="fused", **kw)
    return dataclasses.replace(
        cfg, data=dataclasses.replace(cfg.data, label_ratio=0.5),
        train=dataclasses.replace(cfg.train, base_lr=1e-2))


def test_main_refuses_a_cpu(capsys):
    assert chip_smoke.main([]) == 1
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["ok"] is False and "no TPU" in last["error"]


def test_one_chip_phases_at_tiny_size(capsys):
    chip_smoke.run_one_chip(tiny_config(), n_sample=64)
    records = [json.loads(line)
               for line in capsys.readouterr().out.strip().splitlines()]
    phases = [r["phase"] for r in records]
    assert phases == ["train/build", "graph", "train", "kernel_check[fused]",
                      "blocksparse/build", "blocksparse",
                      "kernel_check[blocksparse]"]
    by = {r["phase"]: r for r in records}
    assert by["graph"]["neighbour_agreement"] == 1.0
    assert by["train"]["replans_swapped"] == 1
    assert not by["kernel_check[fused]"]["layout"]
    assert by["kernel_check[blocksparse]"]["layout"]
    # Same corpus, graph, plan and seed: the block-sparse run's first epoch
    # sees the dense run's batches, and the kernels agree bit for bit.
    assert by["blocksparse"]["loss_per_epoch"][0] == \
        by["train"]["loss_per_epoch"][0]


def test_check_loss_decreases_rejects_a_flat_run():
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.check_loss_decreases([{"loss/total": 2.0},
                                         {"loss/total": 2.0}])


_MESH_SCRIPT = """
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, {root!r})
from tests.test_chip_smoke import tiny_config
import chip_smoke
chip_smoke.mesh_phase(tiny_config(n_epochs=1), n_workers=4)
"""


def test_mesh_phase_on_four_virtual_devices():
    """``--chips 4``'s phase on four CPU devices: sync_mesh shards every
    placed batch four ways and matches the vmapped sequential run."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(ROOT, "src"), ROOT,
                    os.environ.get("PYTHONPATH", "")]))
    out = subprocess.run([sys.executable, "-c",
                          _MESH_SCRIPT.format(root=ROOT)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["phase"] == "mesh" and rec["mesh_size"] == 4
    assert rec["shards_and_devices_per_leaf"] == [[4, 4]]
    assert rec["max_update_rel_err"] <= chip_smoke.MESH_UPDATE_RTOL
