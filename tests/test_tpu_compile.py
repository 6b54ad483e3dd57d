"""Compile rehearsals for TPU v5e: the main path's Pallas kernels at real
widths, and one sync_mesh training chunk on a 2x2 mesh.

Nothing runs: each case compiles for a described (not attached) v5e chip
and checks that the Mosaic kernel is in the executable.  Interpret mode
cannot show these failures — the forward kernels' scalar output in VMEM,
or a Pallas call that the partitioner is asked to split, passed every
CPU test and failed here.

The topology is described inside a fixture, never at import time, so every
pytest worker collects the same tests and only the one running this file
loads the TPU compiler.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.kernels.graph_reg import (graph_reg_blocksparse_bwd_pallas,
                                     graph_reg_blocksparse_pallas,
                                     graph_reg_bwd_pallas,
                                     graph_reg_fused_pallas,
                                     graph_reg_pairwise_pallas)
from repro.kernels.pairwise import knn_topk_pallas
from repro.kernels.tuning import select_tiles

P, C, BT = 4608, 39, 128            # padded meta-batch rows, TIMIT classes
N_GRAPH, D = 65_536, 351            # graph-build corpus rows, frame width


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """Compiles for a described chip cannot be read back from JAX's
    persistent cache; keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *args, kernels=()):
    """Compile ``fn``; its Mosaic kernels carry the fixed ``name=`` of each
    of ``kernels`` as their HLO instruction name (what a trace shows)."""
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    for name in kernels:
        assert f"%{name}" in text, name
    return compiled


def _reg_tiles():
    t = select_tiles("graph_reg", rows=P, backend="tpu")
    return dict(bi=t.bi, bj=t.bj, bc=min(t.bc, C))


def _layout_shapes(sharding):
    """A BlockLayout's 7 index arrays at a full tile list (every tile)."""
    nt = -(-P // BT)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=sharding)
    return [i32(nt * nt)] * 6 + [i32(nt, nt)]


def test_fused_forward_compiles(one_chip):
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                            sharding=one_chip)
    compiled = _compile(
        lambda lp, w: graph_reg_fused_pallas(lp, w, 1.0, 1e-4,
                                             interpret=False, **_reg_tiles()),
        s(P, C), s(P, P), kernels=["graph_reg_fused_reg_forward"])
    # The kernel streams W tile by tile: no B×B temporary.
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * P * P


def test_cross_term_forward_compiles(one_chip):
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                            sharding=one_chip)
    _compile(lambda lp, w: graph_reg_pairwise_pallas(lp, w, bc=C,
                                                     interpret=False),
             s(P, C), s(P, P), kernels=["graph_reg_cross"])


def test_fused_backward_compiles(one_chip):
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                            sharding=one_chip)
    _compile(lambda lp, w, g: graph_reg_bwd_pallas(
        lp, w, g, gamma=1.0, kappa=1e-4, ent_weight=1.0, interpret=False,
        **_reg_tiles()), s(P, C), s(P, P), s(),
        kernels=["graph_reg_bwd_dlogp", "graph_reg_bwd_dw"])


def test_blocksparse_forward_compiles(one_chip):
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                            sharding=one_chip)
    rows, cols, valid = _layout_shapes(one_chip)[:3]
    _compile(lambda lp, w, r, c, v: graph_reg_blocksparse_pallas(
        lp, w, r, c, v, 1.0, 1e-4, bt=BT, bc=C, interpret=False),
        s(P, C), s(P, P), rows, cols, valid,
        kernels=["graph_reg_bsp_forward"])


def test_blocksparse_backward_compiles(one_chip):
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                            sharding=one_chip)
    layout = _layout_shapes(one_chip)
    _compile(lambda lp, w, g, *lay: graph_reg_blocksparse_bwd_pallas(
        lp, w, g, *lay, gamma=1.0, kappa=1e-4, ent_weight=1.0, bt=BT, bc=C,
        interpret=False), s(P, C), s(P, P), s(), *layout,
        kernels=["graph_reg_bsp_bwd_bterm", "graph_reg_bsp_bwd_dlogp",
                 "graph_reg_bsp_bwd_dw"])


def test_knn_topk_compiles(one_chip):
    t = select_tiles("topk", rows=N_GRAPH, backend="tpu")
    x = jax.ShapeDtypeStruct((N_GRAPH, D), jnp.float32, sharding=one_chip)
    _compile(lambda x: knn_topk_pallas(x, x, 10, exclude_self=True,
                                       bi=t.bi, bj=t.bj, bd=t.bd,
                                       interpret=False), x,
             kernels=["knn_topk"])


def test_sync_mesh_chunk_compiles_on_four_chips(topo, monkeypatch):
    """One sync_mesh scan chunk with the fused kernels, k=4 workers on a
    2x2 mesh: the workers' losses run under shard_map, so each chip runs
    its own kernel and only the gradient all-reduce crosses chips."""
    import repro.kernels.graph_reg as graph_reg
    import repro.kernels.ops as ops
    from repro.core.ssl_loss import SSLHyper
    from repro.models.dnn import DNNConfig, init_dnn
    from repro.optim import adagrad
    from repro.train.engine import MESH_AXIS, Engine, TrainState
    from repro.train.train_step import dnn_ssl_step

    # What a TPU backend would decide: compiled kernels, "auto" -> fused.
    monkeypatch.setattr(graph_reg, "_default_interpret", lambda i=None: False)
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    mesh = jax.sharding.Mesh(np.array(topo.devices), (MESH_AXIS,),
                             axis_types=(jax.sharding.AxisType.Auto,))
    cfg = DNNConfig(input_dim=64, hidden_dim=128, n_hidden=2, n_classes=C)
    hyper, opt = SSLHyper(), adagrad()

    def step_fn(s, batch, lr):
        rng, sub = jax.random.split(s.rng)
        p, o, m = dnn_ssl_step(s.params, s.opt_state, batch, cfg=cfg,
                               hyper=hyper, opt=opt, lr=lr, dropout_rng=sub,
                               dropout=0.2, pairwise=ops.graph_regularizer_auto,
                               mesh=mesh)
        return dataclasses.replace(s, params=p, opt_state=o, rng=rng,
                                   step=s.step + 1), m

    engine = Engine(step_fn, strategy="sync_mesh", mesh=mesh, n_workers=4,
                    scan_chunk=2)
    key = jax.random.PRNGKey(0)
    state = jax.eval_shape(lambda: TrainState.create(
        init_dnn(cfg, key), opt.init(init_dnn(cfg, key)), key))
    rep = NamedSharding(mesh, PartitionSpec())
    shard = NamedSharding(mesh, PartitionSpec(None, MESH_AXIS))
    state = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=rep), state)
    S, k, rows = 2, 4, 256
    b = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        shape, dt, sharding=shard)
    batch = {"x": b((S, k, rows, cfg.input_dim)),
             "y": b((S, k, rows), jnp.int32), "label_mask": b((S, k, rows)),
             "W": b((S, k, rows, rows)), "valid": b((S, k, rows), jnp.bool_)}
    lr = jax.ShapeDtypeStruct((), jnp.float32, sharding=rep)
    compiled = engine._chunk_fn.lower(state, batch, lr, False).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" in text
    assert "all-gather" not in text
