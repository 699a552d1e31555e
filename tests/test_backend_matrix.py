"""Cross-backend correctness matrix: segment vs ell vs ti (ISSUE 9).

The three aggregation/compensation backends of ``make_train_step`` must be
interchangeable gradient estimators:

  * full-batch exactness — with the whole graph as one batch there is nothing
    to compensate, so every (backend, fwd_mode, bwd_mode) combination must
    reduce to ``jax.grad`` exactly, at one 128-lane block and (on the kernel
    backends) at two;
  * Fig. 3 bias ordering — the store-free message-invariance estimator
    (backend="ti", DESIGN.md §11) must land in LMC's bias regime and beat
    Cluster-GCN's dropped-halo estimate against the exact backward-SGD oracle;
  * trajectory agreement — 50 SGD steps under ti and ell track each other;
  * store traffic — the ti step provably never reads the historical store
    (NaN-poisoned store changes nothing; the store jaxpr invars are dead) and
    ``store_writes=False`` methods never write it.
"""
import jax
import jax.extend.core
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (LMC, METHODS, MBMethod, TI, backward_sgd_grads,
                        exact_layer_values, from_graph, full_grads,
                        init_history, make_train_step, to_device_batch)
from repro.core.lmc import AGG_BACKENDS
from repro.graph import ClusterSampler
from repro.graph.structure import Graph
from repro.models import make_gnn


def _rel(ga, gb):
    f1 = jax.tree.leaves(ga)
    f2 = jax.tree.leaves(gb)
    num = sum(float(jnp.sum((a - b) ** 2)) for a, b in zip(f1, f2))
    den = sum(float(jnp.sum(jnp.asarray(b) ** 2)) for b in f2)
    return (num / max(den, 1e-12)) ** 0.5


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
def tiny_setup(tiny_graph):
    """The whole graph as one batch, and per hidden width a GCN, its params
    and the ``full_grads`` reference (built on first use)."""
    g = tiny_graph
    data = from_graph(g)
    s = ClusterSampler(g, 1, 1, parts=np.zeros(g.num_nodes, np.int32))
    sg = s.sample()
    assert sg.n_halo_real == 0
    batches = {b: to_device_batch(sg, backend=b) for b in AGG_BACKENDS}
    models = {}

    def at_width(hidden):
        if hidden not in models:
            gnn = make_gnn("gcn", g.feature_dim, hidden, g.num_classes, 2)
            params = gnn.init_params(jax.random.key(0))
            loss_ref, grads_ref = full_grads(gnn, params, data)
            models[hidden] = gnn, params, float(loss_ref), grads_ref
        return models[hidden]

    return g, data, at_width, batches


# --------------------------------------------- (a) full-batch == jax.grad
# 256 lanes: every kernel runs over two 128-lane blocks
_WIDTHS = {"segment": [16], "ell": [16, 256], "ti": [16, 256]}
_MATRIX = [(bk, f, b, d)
           for bk in AGG_BACKENDS
           for f in ("lmc", "historical", "fresh", "none")
           for b in ("lmc", "none", "fresh")
           for d in _WIDTHS[bk]]


@pytest.mark.parametrize(
    "backend,fwd_mode,bwd_mode,hidden", _MATRIX,
    ids=[f"{bk}-f_{f}-b_{b}-d_{d}" for bk, f, b, d in _MATRIX])
def test_full_batch_matrix_reduces_to_autodiff(backend, fwd_mode, bwd_mode,
                                               hidden, tiny_setup):
    """Whole graph in one batch => no halo => every combination is exact.

    Steps run unjitted: the 60-combination product would otherwise pay one
    XLA compilation each for identical numerics.
    """
    g, data, at_width, batches = tiny_setup
    gnn, params, loss_ref, grads_ref = at_width(hidden)
    m = MBMethod("matrix", fwd_mode=fwd_mode, bwd_mode=bwd_mode,
                 store_writes=(backend != "ti"))
    step = make_train_step(gnn, m, g.num_nodes, backend=backend)
    store = init_history(gnn.num_layers, g.num_nodes, hidden)
    loss, grads, _, _ = step(params, store, batches[backend], data.x,
                             data.self_w)
    assert abs(float(loss) - loss_ref) < 1e-5
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(grads_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=1e-6)


# --------------------------------------------- (b) Fig. 3 bias ordering + ti
def test_bias_ordering_ti_in_lmc_regime(small_graph, small_parts):
    """bias(ti) ≈ bias(LMC) < bias(Cluster) vs the exact backward-SGD oracle
    (the Fig. 3 harness of test_lmc_core, extended with the ti backend)."""
    g = small_graph
    data = from_graph(g)
    gnn = make_gnn("gcn", g.feature_dim, 32, g.num_classes, 3)
    params = gnn.init_params(jax.random.key(0))
    hs, vs = exact_layer_values(gnn, params, data)
    biases = {}
    for name, backend in (("lmc", "segment"), ("ti", "ti"),
                          ("cluster", "segment")):
        m = METHODS[name]
        s = ClusterSampler(g, 16, 2, parts=small_parts, seed=1,
                           include_halo=m.include_halo,
                           edge_weight_mode=m.edge_weight_mode,
                           stochastic=False)
        step = jax.jit(make_train_step(gnn, m, g.num_nodes, backend=backend))
        store = init_history(gnn.num_layers, g.num_nodes, 32)
        for _ in range(3):   # warm the store (no-op for the store-free ti)
            for sg in s.epoch():
                _, _, store, _ = step(params, store,
                                      to_device_batch(sg, backend=backend),
                                      data.x, data.self_w)
        errs = []
        for sg in s.epoch():
            _, gm, store, _ = step(params, store,
                                   to_device_batch(sg, backend=backend),
                                   data.x, data.self_w)
            nodes = jnp.asarray(sg.batch_gids[sg.batch_mask > 0])
            gsgd = backward_sgd_grads(gnn, params, data, hs, vs, nodes,
                                      scale=8.0)
            errs.append(_rel(gm["layers"], gsgd))
        biases[name] = float(np.mean(errs))
    # ti must clearly beat the uncompensated estimator and land within a
    # small constant of warmed-store LMC (it trades store reads for the
    # message-invariance approximation, so some headroom is expected)
    assert biases["ti"] < 0.5 * biases["cluster"], biases
    assert biases["ti"] < 4.0 * biases["lmc"], biases


# --------------------------------------------- (c) 50-step loss trajectories
def _run_trajectory(g, data, gnn, method, backend, small_parts, steps=50):
    params = gnn.init_params(jax.random.key(0))
    s = ClusterSampler(g, 16, 2, parts=small_parts, seed=1,
                       stochastic=False)
    step = jax.jit(make_train_step(gnn, method, g.num_nodes, backend=backend))
    store = init_history(gnn.num_layers, g.num_nodes, gnn.hidden_dim)
    losses, i = [], 0
    while len(losses) < steps:
        for sg in s.epoch():
            if len(losses) >= steps:
                break
            loss, grads, store, _ = step(params, store,
                                         to_device_batch(sg, backend=backend),
                                         data.x, data.self_w)
            params = jax.tree.map(lambda p, gr: p - 0.2 * gr, params, grads)
            losses.append(float(loss))
            i += 1
    return np.asarray(losses)


def test_ti_and_ell_loss_trajectories_agree(small_graph, small_parts):
    """50 SGD steps: the store-free ti estimator follows the ell (historical
    compensation) trajectory — same descent, close terminal loss."""
    g = small_graph
    data = from_graph(g)
    gnn = make_gnn("gcn", g.feature_dim, 32, g.num_classes, 2)
    tr_ell = _run_trajectory(g, data, gnn, LMC, "ell", small_parts)
    tr_ti = _run_trajectory(g, data, gnn, TI, "ti", small_parts)
    assert tr_ell[-5:].mean() < 0.85 * tr_ell[:5].mean()  # both actually train
    assert tr_ti[-5:].mean() < 0.85 * tr_ti[:5].mean()
    # terminal losses agree within tolerance
    tail_gap = abs(tr_ti[-10:].mean() - tr_ell[-10:].mean()) \
        / tr_ell[-10:].mean()
    assert tail_gap < 0.10, (tail_gap, tr_ell[-10:].mean(), tr_ti[-10:].mean())
    # trajectories stay close pointwise on average, not just at the end
    rel = np.abs(tr_ti - tr_ell) / np.abs(tr_ell)
    assert float(rel.mean()) < 0.10, float(rel.mean())


# --------------------------------------------- (d) zero store reads / writes
def test_ti_step_never_reads_the_store(tiny_graph):
    """Functional + structural proof of zero historical-store reads.

    Functional: a NaN-poisoned store yields bit-identical loss/grads to a
    zero store. Structural: in the step's jaxpr the store input vars feed no
    equation — they only pass through to the output untouched.
    """
    g = tiny_graph
    data = from_graph(g)
    gnn = make_gnn("gcn", g.feature_dim, 16, g.num_classes, 2)
    params = gnn.init_params(jax.random.key(0))
    rng = np.random.default_rng(1)
    parts = rng.integers(0, 4, g.num_nodes).astype(np.int32)
    s = ClusterSampler(g, 4, 1, parts=parts, seed=0)
    sg = s.sample()
    assert sg.n_halo_real > 0        # the compensation path is actually live
    batch = to_device_batch(sg, backend="ti")
    step = make_train_step(gnn, TI, g.num_nodes, backend="ti")

    store0 = init_history(2, g.num_nodes, 16)
    store_nan = jax.tree.map(lambda x: jnp.full_like(x, jnp.nan), store0)
    l0, g0, out0, _ = step(params, store0, batch, data.x, data.self_w)
    l1, g1, out1, _ = step(params, store_nan, batch, data.x, data.self_w)
    assert float(l0) == float(l1) and np.isfinite(float(l0))
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # store_writes=False: the store rides through bit-identical (NaNs intact)
    for a, b in zip(jax.tree.leaves(store_nan), jax.tree.leaves(out1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    closed = jax.make_jaxpr(step)(params, store0, batch, data.x, data.self_w)
    flat, _ = jax.tree_util.tree_flatten(
        (params, store0, batch, data.x, data.self_w))
    store_leaves = jax.tree_util.tree_leaves(store0)
    store_vars = {id(closed.jaxpr.invars[i]) for i, a in enumerate(flat)
                  if any(a is sl for sl in store_leaves)}
    assert len(store_vars) == len(store_leaves)
    used = {id(v) for eqn in closed.jaxpr.eqns for v in eqn.invars
            if not isinstance(v, jax.extend.core.Literal)}
    assert not (store_vars & used), "ti step consumed a store input"


def test_store_writes_gating_is_orthogonal_to_backend(tiny_graph):
    """``store_writes=False`` freezes the store under any backend (here: ell,
    which *reads* it), while a store-writing method on backend="ti" refreshes
    batch rows without its gradients ever depending on the store."""
    g = tiny_graph
    data = from_graph(g)
    gnn = make_gnn("gcn", g.feature_dim, 16, g.num_classes, 2)
    params = gnn.init_params(jax.random.key(0))
    rng = np.random.default_rng(1)
    parts = rng.integers(0, 4, g.num_nodes).astype(np.int32)
    s = ClusterSampler(g, 4, 1, parts=parts, seed=0)
    sg = s.sample()
    store = init_history(2, g.num_nodes, 16)

    frozen = MBMethod("lmc_frozen", fwd_mode="lmc", bwd_mode="lmc",
                      store_writes=False)
    step = make_train_step(gnn, frozen, g.num_nodes, backend="ell")
    _, _, out, _ = step(params, store, to_device_batch(sg, backend="ell"),
                        data.x, data.self_w)
    for a, b in zip(jax.tree.leaves(store), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    batch = to_device_batch(sg, backend="ti")
    step_w = make_train_step(gnn, LMC, g.num_nodes, backend="ti")
    step_ro = make_train_step(gnn, TI, g.num_nodes, backend="ti")
    _, gw, out_w, _ = step_w(params, store, batch, data.x, data.self_w)
    _, gro, _, _ = step_ro(params, store, batch, data.x, data.self_w)
    changed = np.where(np.any(np.asarray(out_w.h[0]) != 0, axis=-1))[0]
    in_batch = set(sg.batch_gids[sg.batch_mask > 0].tolist())
    assert len(changed) and set(changed.tolist()) <= in_batch
    for a, b in zip(jax.tree.leaves(gw), jax.tree.leaves(gro)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_backend_requires_its_batch_fields(tiny_graph):
    g = tiny_graph
    data = from_graph(g)
    gnn = make_gnn("gcn", g.feature_dim, 16, g.num_classes, 2)
    params = gnn.init_params(jax.random.key(0))
    s = ClusterSampler(g, 1, 1, parts=np.zeros(g.num_nodes, np.int32))
    sg = s.sample()
    store = init_history(2, g.num_nodes, 16)
    step = make_train_step(gnn, TI, g.num_nodes, backend="ti")
    with pytest.raises(ValueError, match="batch.ell"):
        step(params, store, to_device_batch(sg), data.x, data.self_w)
    ell_only = to_device_batch(sg, backend="ell")
    with pytest.raises(ValueError, match="batch.ti_scale"):
        step(params, store, ell_only, data.x, data.self_w)
