"""The "ell" aggregation backend: the Pallas kernel train step must be a
drop-in for the jnp segment-sum step — same loss, same grads, same store
updates — with every batch of a sampler hitting one jit trace."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (GAS, LMC, from_graph, init_history, make_train_step,
                        to_device_batch)
from repro.graph import ClusterSampler
from repro.graph.structure import Graph
from repro.models import make_gnn


@pytest.fixture(scope="module")
def tiny_graph():
    rng = np.random.default_rng(0)
    n, e = 300, 1200
    x = rng.normal(size=(n, 12)).astype(np.float32)
    y = rng.integers(0, 5, n).astype(np.int32)
    tm = rng.random(n) < 0.6
    vm = (~tm) & (rng.random(n) < 0.5)
    return Graph.from_edges(n, rng.integers(0, n, e), rng.integers(0, n, e),
                            x, y, tm, vm, ~(tm | vm))


@pytest.fixture(scope="module")
def tiny_parts(tiny_graph):
    rng = np.random.default_rng(1)
    return rng.integers(0, 4, tiny_graph.num_nodes).astype(np.int32)


@pytest.mark.parametrize("hidden", [16, 256], ids=lambda d: f"hidden{d}")
@pytest.mark.parametrize("method", [LMC, GAS], ids=lambda m: m.name)
def test_ell_step_matches_segment(method, hidden, tiny_graph, tiny_parts):
    """At hidden 256 every kernel runs over two 128-lane blocks."""
    g = tiny_graph
    data = from_graph(g)
    gnn = make_gnn("gcn", g.feature_dim, hidden, g.num_classes, 2)
    params = gnn.init_params(jax.random.key(0))
    s = ClusterSampler(g, 4, 1, parts=tiny_parts, seed=0,
                       include_halo=method.include_halo,
                       edge_weight_mode=method.edge_weight_mode)
    step_seg = jax.jit(make_train_step(gnn, method, g.num_nodes))
    step_ell = jax.jit(make_train_step(gnn, method, g.num_nodes,
                                       backend="ell"))
    st_seg = st_ell = init_history(2, g.num_nodes, hidden)
    for _ in range(2):   # chained steps: store updates feed the next batch
        sg = s.sample()
        l1, g1, st_seg, _ = step_seg(params, st_seg, to_device_batch(sg),
                                     data.x, data.self_w)
        l2, g2, st_ell, _ = step_ell(params, st_ell,
                                     to_device_batch(sg, backend="ell"),
                                     data.x, data.self_w)
        np.testing.assert_allclose(float(l1), float(l2), rtol=1e-4)
        for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=1e-6)
        np.testing.assert_allclose(np.asarray(st_seg.h), np.asarray(st_ell.h),
                                   rtol=2e-4, atol=1e-6)
        np.testing.assert_allclose(np.asarray(st_seg.v), np.asarray(st_ell.v),
                                   rtol=2e-4, atol=1e-6)


def test_ell_batches_share_one_trace(tiny_graph, tiny_parts):
    """Fixed per-bucket capacities: every batch of a sampler has identical
    ELL shapes, so the jit'd step compiles exactly once per sampler."""
    s = ClusterSampler(tiny_graph, 4, 1, parts=tiny_parts, seed=0)
    shapes = []
    for _ in range(3):
        b = to_device_batch(s.sample(), backend="ell")
        shapes.append(jax.tree.map(lambda x: jnp.shape(x), b))
    assert shapes[0] == shapes[1] == shapes[2]


def test_ell_step_requires_ell_batch(tiny_graph, tiny_parts):
    g = tiny_graph
    data = from_graph(g)
    gnn = make_gnn("gcn", g.feature_dim, 16, g.num_classes, 2)
    params = gnn.init_params(jax.random.key(0))
    s = ClusterSampler(g, 4, 1, parts=tiny_parts, seed=0)
    step = make_train_step(gnn, LMC, g.num_nodes, backend="ell")
    store = init_history(2, g.num_nodes, 16)
    with pytest.raises(ValueError, match="batch.ell"):
        step(params, store, to_device_batch(s.sample()), data.x, data.self_w)
