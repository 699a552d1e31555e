"""The padded edge capacity of a training batch: the top-c volume sums,
capped at the graph's own directed edge count, and what the capped batches
still do on every aggregation backend."""
import jax
import numpy as np
import pytest

from repro.core import (CLUSTER, LMC, from_graph, host_batch, init_history,
                        make_train_step, to_device_batch)
from repro.graph import ClusterSampler
from repro.kernels.ops import fixed_row_capacity
from repro.models import make_gnn

NUM_PARTS = 16


def _round_up(x, m):
    return -(-int(x) // m) * m


def _top_c_edges(graph, parts, c):
    """The uncapped sizing, from scratch: the c largest per-part volumes plus
    the c largest per-part halo volumes (+64, rounded to 256)."""
    deg = graph.degrees()
    vol = np.bincount(parts, weights=deg, minlength=NUM_PARTS)
    halo_vol = np.zeros(NUM_PARTS)
    for p in range(NUM_PARTS):
        members = np.flatnonzero(parts == p)
        nbrs = np.unique(np.concatenate([graph.neighbors(v) for v in members]))
        halo_vol[p] = deg[nbrs[parts[nbrs] != p]].sum()
    top = np.sort(vol)[::-1][:c].sum() + np.sort(halo_vol)[::-1][:c].sum()
    return _round_up(top + 64, 256)


@pytest.mark.parametrize("c", [1, NUM_PARTS // 2, NUM_PARTS],
                         ids=["one-part", "half-the-parts", "all-parts"])
def test_pad_edges_is_capped_at_the_graph_edge_count(small_graph, small_parts,
                                                     c):
    g = small_graph
    cap = _round_up(g.num_edges, 256)
    uncapped = _top_c_edges(g, small_parts, c)
    s = ClusterSampler(g, NUM_PARTS, c, parts=small_parts, seed=4)
    assert s.pad_edges == min(uncapped, cap)
    real = []
    for _ in range(2):          # two shuffled epochs
        for sg in s.epoch():
            assert sg.edge_src.shape[0] == s.pad_edges
            assert sg.n_edges_real <= s.pad_edges
            assert np.all(sg.edge_w[sg.n_edges_real:] == 0)
            real.append(sg.n_edges_real)
    assert len(real) == 2 * (NUM_PARTS // c)
    if c == 1:
        # the top-c sum is below the graph's edges: the cap does not engage
        assert uncapped < cap and s.pad_edges == uncapped
    if c == NUM_PARTS // 2:
        assert uncapped > cap and s.pad_edges == cap
    if c == NUM_PARTS:
        # the whole graph in one batch: every directed edge, once
        assert real == [g.num_edges] * 2


@pytest.mark.parametrize("backend,method", [("ell", LMC), ("ti", CLUSTER)],
                         ids=["ell-lmc", "ti-cluster"])
def test_kernel_backends_run_every_capped_batch_like_segment(
        small_graph, small_parts, backend, method):
    """At half the parts the cap binds; each batch of an epoch still fits the
    ELL layout's fixed bucket capacities, and the step's loss and gradients
    equal the segment backend's (``ti`` under a method that compensates
    nothing, where the two estimators coincide)."""
    g = small_graph
    s = ClusterSampler(g, NUM_PARTS, NUM_PARTS // 2, parts=small_parts,
                       seed=5)
    assert s.pad_edges == _round_up(g.num_edges, 256)
    data = from_graph(g)
    gnn = make_gnn("gcn", g.feature_dim, 16, g.num_classes, 2)
    params = gnn.init_params(jax.random.key(0))
    store = init_history(gnn.num_layers, g.num_nodes, 16)
    store = jax.tree.map(
        lambda a: jax.random.normal(jax.random.key(1), a.shape, a.dtype),
        store)
    steps = {b: jax.jit(make_train_step(gnn, method, g.num_nodes, backend=b))
             for b in ("segment", backend)}
    rows_ext = s.pad_batch + s.pad_halo
    caps = fixed_row_capacity(rows_ext, s.pad_edges,
                              degree_hists=s.degree_hists)
    worst = fixed_row_capacity(rows_ext, s.pad_edges)
    assert all(c <= w for c, w in zip(caps, worst))
    n = 0
    for sg in s.epoch():
        # built with the fixed bucket capacities of the capped edge count and
        # the graph's degree profile, never above the worst case
        # (ell_from_coo raises where a bucket would overflow them)
        batches = {b: to_device_batch(sg, backend=b) for b in steps}
        rows = tuple(r.shape[0] for r in batches[backend].ell.bucket_rows)
        assert rows == caps
        out = {b: steps[b](params, store, batches[b], data.x, data.self_w)
               for b in steps}
        (ls, gs, _, _), (lb, gb, _, _) = out["segment"], out[backend]
        np.testing.assert_allclose(float(lb), float(ls), rtol=1e-5)
        for a, b in zip(jax.tree.leaves(gb), jax.tree.leaves(gs)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=3e-4, atol=1e-6)
        n += 1
    assert n == 2


def test_host_batch_shapes_follow_the_capped_capacity(small_graph,
                                                      small_parts):
    s = ClusterSampler(small_graph, NUM_PARTS, NUM_PARTS // 2,
                       parts=small_parts, seed=6)
    sg = s.sample()
    hb = host_batch(sg, backend="segment")
    assert hb.edge_src.shape == hb.edge_dst.shape == hb.edge_w.shape \
        == (s.pad_edges,)
    assert sg.edge_fill == sg.n_edges_real / s.pad_edges
    assert 0.9 < sg.edge_fill <= 1.0
