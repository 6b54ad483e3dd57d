"""Sharding spec rules (pure) + a subprocess mini dry-run on 8 host devices."""
import json
import os
import subprocess
import sys

import jax
import pytest

from repro.launch import hlo_analysis
from repro.sharding.specs import spec_for_cache, spec_for_param


class _FakeMesh:
    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)


MESH = _FakeMesh({"data": 16, "model": 16})
MESH_MP = _FakeMesh({"pod": 2, "data": 16, "model": 16})


def _norm(spec):
    """PartitionSpec equality ignoring trailing Nones."""
    t = tuple(spec)
    while t and t[-1] is None:
        t = t[:-1]
    return t


def test_dp_replicates_everything():
    assert _norm(spec_for_param("embed/table", (151936, 1536), MESH,
                                "dp")) == ()
    assert _norm(spec_for_param("superblocks/0/attn/wq", (28, 1536, 12, 128),
                                MESH, "dp")) == ()


def test_fsdp_shards_largest_divisible_dim():
    s = spec_for_param("embed/table", (151936, 1536), MESH, "fsdp")
    assert _norm(s) == ("data",)                # vocab % 16 == 0
    s = spec_for_param("superblocks/0/mlp/wu", (28, 1536, 8960), MESH, "fsdp")
    assert _norm(s) == (None, None, "data")     # skips the scan dim; d_ff largest
    # non-divisible everything -> replicated
    s = spec_for_param("x/odd", (7, 13), MESH, "fsdp")
    assert _norm(s) == ()


def test_fsdp_tp_assigns_model_axis_by_name():
    s = spec_for_param("superblocks/0/mlp/wu", (28, 1536, 8960), MESH,
                       "fsdp_tp")
    assert _norm(s) == (None, "data", "model")  # tp on d_ff, fsdp on d
    s = spec_for_param("superblocks/0/moe/wu", (32, 8, 4096, 14336), MESH,
                       "fsdp_tp")
    assert s[1] is None and "model" not in (s[1],)  # experts=8 not divisible
    s = spec_for_param("superblocks/0/moe/wu", (60, 384, 7168, 2048), MESH,
                       "fsdp_tp")
    assert _norm(s) == (None, "model", "data")  # expert-parallel (384 % 16)
    s = spec_for_param("lm_head", (4096, 64000), MESH, "fsdp_tp")
    assert _norm(s) == ("data", "model")
    s = spec_for_param("superblocks/0/attn/wq", (48, 4096, 32, 128), MESH,
                       "fsdp_tp")
    assert _norm(s) == (None, "data", "model")  # heads on model


def test_multipod_fsdp_uses_pod_and_data():
    # vocab gets tensor parallel, d_model gets ZeRO over (pod, data)
    s = spec_for_param("embed/table", (151936, 1536), MESH_MP, "fsdp_tp")
    assert _norm(s) == ("model", ("pod", "data"))
    s = spec_for_param("superblocks/0/mlp/wu", (28, 1536, 8960), MESH_MP,
                       "fsdp_tp")
    assert _norm(s) == (None, ("pod", "data"), "model")


def test_cache_specs_batch_vs_sequence_sharding():
    # decode_32k: batch 128 divisible -> batch on data
    s = spec_for_cache("layers/0/k", (32, 128, 32768, 8, 128), MESH, 128,
                       "fsdp_tp")
    assert s[1] == "data"
    # long_500k: batch 1 -> shard the sequence dim instead
    s = spec_for_cache("layers/0/k", (32, 1, 524288, 8, 128), MESH, 1,
                       "fsdp_tp")
    assert s[1] is None and s[2] == "data"


def test_hlo_analysis_trip_counting():
    """Tiny scan of matmuls: analyzer must multiply by known trip count."""
    import jax.numpy as jnp

    def f(x, w):
        def body(c, _):
            return c @ w, None
        y, _ = jax.lax.scan(body, x, None, length=7)
        return y

    x = jnp.ones((64, 64)); w = jnp.ones((64, 64))
    compiled = jax.jit(f).lower(x, w).compile()
    costs = hlo_analysis.analyze_hlo(compiled.as_text())
    want = 7 * 2 * 64 ** 3
    assert costs.flops == pytest.approx(want, rel=0.05), costs.flops


def test_hlo_analysis_collectives_parse():
    txt = """HloModule m, num_partitions=4
ENTRY %main (p: f32[8,16]) -> f32[8,16] {
  %p = f32[8,16]{1,0} parameter(0)
  ROOT %ar = f32[8,16]{1,0} all-reduce(%p), to_apply=%add
}
%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %r = f32[] add(%a, %b)
}
"""
    costs = hlo_analysis.analyze_hlo(txt)
    assert costs.count_by_op["all-reduce"] == 1
    assert costs.bytes_by_op["all-reduce"] == 8 * 16 * 4


@pytest.mark.slow
def test_mini_dryrun_subprocess():
    """End-to-end: lower+compile one arch/shape on an 8-device host mesh."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, json
from repro.launch.inputs import input_specs
from repro.launch.mesh import make_debug_mesh
mesh = make_debug_mesh(data=4, model=2)
spec = input_specs("qwen1.5-0.5b", "decode_32k", mesh, "fsdp_tp")
with mesh:
    compiled = jax.jit(spec["fn"], donate_argnums=spec["donate"]).lower(*spec["args"]).compile()
ca = compiled.cost_analysis()
print(json.dumps({"ok": True, "flops": ca.get("flops", 0)}))
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, cwd=os.path.dirname(
                             os.path.dirname(os.path.abspath(__file__))),
                         timeout=420)
    assert out.returncode == 0, out.stderr[-2000:]
    assert json.loads(out.stdout.strip().splitlines()[-1])["ok"]
