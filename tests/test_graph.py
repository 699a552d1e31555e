"""Graph substrate: structures, partitioner, sampler invariants."""
import numpy as np
from _prop import given, strategies as st

from repro.graph import ClusterSampler, edge_cut_fraction, make_sbm_dataset
from repro.graph.partition import partition_balance
from repro.graph.structure import beta_score


def test_graph_symmetry(small_graph):
    g = small_graph
    # undirected: every edge appears in both directions
    src = np.repeat(np.arange(g.num_nodes), np.diff(g.indptr))
    fwd = set(zip(src.tolist(), g.indices.tolist()))
    assert all((b, a) in fwd for a, b in list(fwd)[:2000])
    assert not any(a == b for a, b in list(fwd)[:2000])


def test_partition_balance_and_cut(small_graph, small_parts):
    assert partition_balance(small_parts, 16) <= 1.06
    # must beat a random partition's cut by a wide margin
    rng = np.random.default_rng(0)
    rand = rng.integers(0, 16, small_graph.num_nodes).astype(np.int32)
    assert edge_cut_fraction(small_graph, small_parts) \
        < 0.8 * edge_cut_fraction(small_graph, rand)


@given(c=st.integers(1, 4), seed=st.integers(0, 5))
def test_sampler_padding_invariants(c, seed):
    g = make_sbm_dataset("ppi-cpu", seed=3)
    s = ClusterSampler(g, 16, c, seed=seed)
    sg = s.sample()
    ne = sg.n_ext
    assert sg.edge_src.max() < ne and sg.edge_dst.max() < ne
    assert sg.batch_mask.sum() == sg.n_batch_real
    assert sg.halo_mask.sum() == sg.n_halo_real
    # padded edges carry zero weight
    assert np.all(sg.edge_w[sg.n_edges_real:] == 0)
    # batch and halo are disjoint
    b = set(sg.batch_gids[sg.batch_mask > 0].tolist())
    h = set(sg.halo_gids[sg.halo_mask > 0].tolist())
    assert not (b & h)


def test_epoch_covers_every_cluster(small_graph, small_parts):
    s = ClusterSampler(small_graph, 16, 2, parts=small_parts, seed=0)
    seen = set()
    for sg in s.epoch():
        seen.update(sg.batch_gids[sg.batch_mask > 0].tolist())
    assert len(seen) == small_graph.num_nodes


def test_subgraph_edges_match_graph(small_graph, small_parts):
    s = ClusterSampler(small_graph, 16, 1, parts=small_parts, seed=0)
    sg = s.sample()
    g = small_graph
    gids = np.concatenate([sg.batch_gids, sg.halo_gids])
    # every real edge exists in the original graph
    for e in range(0, sg.n_edges_real, 97):
        u, v = gids[sg.edge_src[e]], gids[sg.edge_dst[e]]
        assert u in g.neighbors(v)


@given(seed=st.integers(0, 20), qseed=st.integers(0, 20))
def test_epoch_schedule_covers_under_shuffled_queries(seed, qseed, small_graph,
                                                     small_parts):
    """``clusters_at(i, mode="epoch")`` is pure in (seed, i): querying the
    slots of any epoch in arbitrary (concurrent-style) shuffled order still
    yields every cluster exactly once per epoch, and repeated queries of the
    same slot agree."""
    s = ClusterSampler(small_graph, 16, 3, parts=small_parts, seed=seed)
    bpe = s.batches_per_epoch            # 16 // 3 = 5 slots, 15 clusters/epoch
    q = np.random.default_rng(qseed)
    for epoch in range(3):
        slots = epoch * bpe + q.permutation(bpe)     # shuffled query order
        got = np.concatenate([s.clusters_at(int(i), mode="epoch")
                              for i in slots])
        assert len(got) == bpe * s.c
        assert len(np.unique(got)) == bpe * s.c      # no cluster twice
        # replay: the same slot queried again returns the same ids
        i = int(slots[0])
        np.testing.assert_array_equal(s.clusters_at(i, mode="epoch"),
                                      s.clusters_at(i, mode="epoch"))


@given(score=st.sampled_from(["x2", "2x-x2", "x", "1", "sin"]),
       alpha=st.floats(0.0, 1.0))
def test_beta_scores_in_unit_interval(score, alpha):
    ld = np.array([0, 1, 5, 10])
    gd = np.array([1, 2, 5, 100])
    b = beta_score(ld, gd, score, alpha)
    assert np.all(b >= 0) and np.all(b <= 1)
