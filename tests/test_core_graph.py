"""Affinity graph, partitioner, meta-batch synthesis — unit + property tests."""
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import (build_affinity_graph, edge_cut, partition_graph,
                        partition_permutation)
from repro.core.affinity import knn_edges, pairwise_sq_dists
from repro.core.metabatch import NeighborSampler, batch_graph
from repro.core.stats import (batch_label_entropy, connectivity_distribution,
                              entropy_distribution, random_batches)


# ----------------------------------------------------------------- affinity
def test_pairwise_sq_dists_matches_numpy(rng):
    X = rng.normal(size=(40, 7))
    Y = rng.normal(size=(25, 7))
    d2 = pairwise_sq_dists(X, Y)
    ref = ((X[:, None] - Y[None]) ** 2).sum(-1)
    np.testing.assert_allclose(d2, ref, atol=1e-8)


def test_knn_exactness_against_bruteforce(rng):
    X = rng.normal(size=(150, 10))
    src, dst, d2 = knn_edges(X, 5, block=32)
    full = pairwise_sq_dists(X, X)
    np.fill_diagonal(full, np.inf)
    for i in range(150):
        mine = set(dst[src == i])
        ref = set(np.argsort(full[i])[:5])
        # allow ties at the boundary
        assert len(mine & ref) >= 4


def test_affinity_graph_symmetric_zero_diag(small_graph_setup):
    _, graph, _ = small_graph_setup
    W = graph.W
    assert (W != W.T).nnz == 0
    assert W.diagonal().sum() == 0
    assert W.data.min() > 0 and W.data.max() <= 1.0 + 1e-9
    # every node has at least k neighbours after symmetrization
    assert graph.neighbor_counts().min() >= graph.k


def test_permuted_graph_preserves_weights(small_graph_setup):
    _, graph, plan = small_graph_setup
    perm = partition_permutation(plan.mini_block_labels)
    gp = graph.permuted(perm)
    assert gp.W.nnz == graph.W.nnz
    np.testing.assert_allclose(gp.W.sum(dtype=np.float64),
                               graph.W.sum(dtype=np.float64), rtol=1e-9)
    # spot check: entry (a, b) in permuted == (perm[a], perm[b]) in original
    a, b = 3, 17
    np.testing.assert_allclose(gp.W[a, b], graph.W[perm[a], perm[b]])


def test_dense_block_matches_csr(small_graph_setup):
    _, graph, _ = small_graph_setup
    idx = np.arange(0, 60, 2)
    blk = graph.dense_block(idx)
    ref = np.asarray(graph.W[idx][:, idx].todense())
    np.testing.assert_allclose(blk, ref, atol=1e-7)


@pytest.mark.parametrize("size", [None, 64])
def test_sparse_block_scatters_to_dense_block(small_graph_setup, size):
    _, graph, _ = small_graph_setup
    idx = np.concatenate([np.arange(0, 60, 2), np.arange(300, 320)])
    dense = graph.dense_block(idx)
    blk = graph.sparse_block(idx, size)
    n = len(idx) if size is None else size
    assert blk.shape == (n, n) and blk.vals.dtype == np.float32
    assert len(blk.vals) == np.count_nonzero(dense)
    out = np.zeros((n, n), np.float32)
    blk.scatter_into(out)
    want = np.zeros((n, n), np.float32)
    want[:len(idx), :len(idx)] = dense
    assert out.tobytes() == want.tobytes()
    assert np.asarray(blk).tobytes() == want.tobytes()


# ---------------------------------------------------------------- partition
def test_partition_balanced_and_better_than_random(small_graph_setup):
    _, graph, _ = small_graph_setup
    k = 12
    res = partition_graph(graph.W, k, tol=0.15, seed=0)
    n = graph.n_nodes
    assert res.sizes.sum() == n
    assert res.sizes.max() <= int(np.ceil(n / k * 1.3))
    # min-cut partitioning beats a random balanced split decisively
    rng = np.random.default_rng(0)
    rand_labels = rng.permutation(np.arange(n) % k)
    assert res.cut < 0.7 * edge_cut(graph.W, rand_labels)


@settings(max_examples=15, deadline=None)
@given(n=st.integers(20, 120), k=st.integers(2, 6), seed=st.integers(0, 5))
def test_partition_properties(n, k, seed):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, 4))
    g = build_affinity_graph(X, k=4)
    res = partition_graph(g.W, k, tol=0.3, seed=seed)
    assert res.labels.shape == (n,)
    assert res.labels.min() >= 0 and res.labels.max() < k
    assert res.sizes.sum() == n
    perm = partition_permutation(res.labels)
    assert sorted(perm) == list(range(n))
    # permutation groups labels contiguously
    assert (np.diff(res.labels[perm]) >= 0).all()


# --------------------------------------------------------------- metabatch
def test_meta_batches_partition_the_dataset(small_graph_setup):
    corpus, _, plan = small_graph_setup
    allidx = np.concatenate(plan.meta_batches)
    assert sorted(allidx) == list(range(corpus.n))  # exactly-once cover


def test_meta_batch_sizes_near_B(small_graph_setup):
    _, _, plan = small_graph_setup
    sizes = np.array([len(m) for m in plan.meta_batches])
    assert (sizes > 0.5 * plan.batch_size).all()
    assert (sizes < 1.9 * plan.batch_size).all()


def test_meta_batches_improve_connectivity_vs_random(small_graph_setup):
    corpus, graph, plan = small_graph_setup
    rng = np.random.default_rng(1)
    c_meta = connectivity_distribution(graph, plan.meta_batches)
    c_rand = connectivity_distribution(
        graph, random_batches(corpus.n, plan.batch_size, rng=rng))
    assert c_meta.mean() > 2.0 * c_rand.mean()


def test_meta_batch_entropy_recovers_toward_global(small_graph_setup):
    """Fig 2a: meta-batches ≈ global entropy, mini-blocks are much lower."""
    corpus, graph, plan = small_graph_setup
    glob = batch_label_entropy(corpus.y, np.arange(corpus.n), corpus.n_classes)
    e_meta = entropy_distribution(corpus.y, plan.meta_batches,
                                  corpus.n_classes)
    blocks = [np.where(plan.mini_block_labels == b)[0]
              for b in range(plan.mini_block_labels.max() + 1)]
    e_mini = entropy_distribution(corpus.y, blocks, corpus.n_classes)
    assert e_meta.mean() > e_mini.mean()
    assert e_meta.mean() > 0.75 * glob


def test_neighbor_sampler_eq6(small_graph_setup):
    _, graph, plan = small_graph_setup
    s = NeighborSampler(plan.batch_edges, seed=0)
    for i in range(plan.n_meta):
        nbrs, p = s.probs(i)
        if len(nbrs):
            np.testing.assert_allclose(p.sum(), 1.0)
            assert (p > 0).all()
            j = s.sample(i)
            assert j in set(nbrs.tolist())
    # Eq 6: probability proportional to |C_ij|
    E = plan.batch_edges
    i = int(np.argmax(np.diff(E.indptr)))
    nbrs, p = s.probs(i)
    w = np.array([E[i, j] for j in nbrs])
    np.testing.assert_allclose(p, w / w.sum())


def test_batch_graph_counts_cross_edges(small_graph_setup):
    corpus, graph, plan = small_graph_setup
    meta_of_node = plan.meta_of_block[plan.mini_block_labels]
    E = batch_graph(graph, meta_of_node, plan.n_meta)
    # total cross-meta edge count equals the complement of within-batch edges
    coo = graph.W.tocoo()
    cross = (meta_of_node[coo.row] != meta_of_node[coo.col]).sum() / 2
    np.testing.assert_allclose(E.sum(), 2 * cross / 2)  # symmetric storage
    assert (E != E.T).nnz == 0


def test_meta_batch_connectivity_variance_reduction(small_graph_setup):
    """§2.1: Var[C_meta] ≈ Var[C_mini]/K, mean preserved (Fig 2b)."""
    corpus, graph, plan = small_graph_setup
    blocks = [np.where(plan.mini_block_labels == b)[0]
              for b in range(plan.mini_block_labels.max() + 1)]
    c_mini = connectivity_distribution(graph, blocks)
    c_meta = connectivity_distribution(graph, plan.meta_batches)
    assert c_meta.mean() >= 0.8 * c_mini.mean()    # E[C_meta] >= E[C_mini] (approx)
    assert c_meta.std() < c_mini.std()             # variance shrinks
