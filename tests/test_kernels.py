"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps,
gradient paths through the custom VJPs, and the vectorized ELL builder."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _prop import given, settings, strategies as st

from repro.kernels import (ELLCapacityError, ELLGraph, build_ell,
                           bucketed_spmm, default_interpret, ell_aggregate_fn,
                           ell_from_coo, ell_spmm, lmc_compensate)
from repro.kernels.ops import _build_ell_loop
from repro.kernels.ref import (degree_bucket_spmm_ref, ell_spmm_ref,
                               lmc_compensate_ref)


def _random_csr(seed, n_max=60, heavy=True):
    """Random CSR with deg-0 rows and (optionally) heavy rows > max bucket."""
    r = np.random.default_rng(seed)
    n = int(r.integers(5, n_max))
    choices = [0, 1, 3, 7, 8, 20] + ([130, 300] if heavy else [])
    p = np.ones(len(choices)) / len(choices)
    deg = r.choice(choices, size=n, p=p)
    indptr = np.zeros(n + 1, np.int64)
    indptr[1:] = np.cumsum(deg)
    nnz = int(indptr[-1])
    indices = r.integers(0, n, nnz).astype(np.int32)
    weights = r.random(nnz).astype(np.float32)
    return indptr, indices, weights


# the streamed kernels at one lane block and at the published widths: every
# row copy reads a 128-lane window of the source's HBM tiles (hbm_tiles)
WIDTHS = [128, 256, 384]


@pytest.mark.parametrize("d", WIDTHS)
@given(n_tiles=st.integers(1, 2), k=st.sampled_from([4, 8, 32]),
       m=st.sampled_from([64, 300, 1000]),
       dtype=st.sampled_from([np.float32]), seed=st.integers(0, 100))
@settings(max_examples=16)
def test_ell_spmm_matches_ref(d, n_tiles, k, m, dtype, seed):
    rng = np.random.default_rng(seed)
    n = 256 * n_tiles
    idx = rng.integers(0, m, (n, k)).astype(np.int32)
    w = (rng.random((n, k)) * (rng.random((n, k)) > 0.3)).astype(dtype)
    h = rng.normal(size=(m, d)).astype(dtype)
    out = ell_spmm(jnp.asarray(idx), jnp.asarray(w), jnp.asarray(h))
    ref = ell_spmm_ref(jnp.asarray(idx), jnp.asarray(w), jnp.asarray(h))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ell_spmm_bf16():
    rng = np.random.default_rng(0)
    idx = rng.integers(0, 64, (256, 8)).astype(np.int32)
    w = rng.random((256, 8)).astype(np.float32)
    h = rng.normal(size=(64, 128)).astype(jnp.bfloat16)
    out = ell_spmm(jnp.asarray(idx), jnp.asarray(w).astype(jnp.bfloat16),
                   jnp.asarray(h))
    ref = ell_spmm_ref(jnp.asarray(idx),
                       jnp.asarray(w).astype(jnp.bfloat16), jnp.asarray(h))
    np.testing.assert_allclose(np.float32(out), np.float32(ref),
                               rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("d", WIDTHS)
@given(seed=st.integers(0, 50), beta_max=st.floats(0.0, 1.0))
@settings(max_examples=10)
def test_lmc_compensate_matches_ref(d, seed, beta_max):
    rng = np.random.default_rng(seed)
    n, m = 256, 500
    store = rng.normal(size=(m, d)).astype(np.float32)
    gids = rng.integers(0, m, n).astype(np.int32)
    beta = (rng.random(n) * beta_max).astype(np.float32)
    mask = (rng.random(n) > 0.2).astype(np.float32)
    fresh = rng.normal(size=(n, d)).astype(np.float32)
    args = [jnp.asarray(a) for a in (store, gids, beta, fresh, mask)]
    np.testing.assert_allclose(np.asarray(lmc_compensate(*args)),
                               np.asarray(lmc_compensate_ref(*args)),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("stream", [True, False], ids=["streamed", "resident"])
def test_lmc_compensate_across_id_blocks_and_feature_tiles(stream):
    """Six row tiles whose ids span two 1024-word SMEM blocks, times two
    feature tiles: the streamed kernel's next-tile DMA crosses both a
    feature-tile and an id-block boundary."""
    rng = np.random.default_rng(0)
    n, m, d = 1300, 700, 256
    args = [jnp.asarray(a) for a in (
        rng.normal(size=(m, d)).astype(np.float32),
        rng.integers(0, m, n).astype(np.int32),
        rng.random(n).astype(np.float32),
        rng.normal(size=(n, d)).astype(np.float32),
        (rng.random(n) > 0.2).astype(np.float32))]
    np.testing.assert_allclose(
        np.asarray(lmc_compensate(*args, stream=stream)),
        np.asarray(lmc_compensate_ref(*args)), rtol=1e-6, atol=1e-6)


def test_bucketed_spmm_on_real_graph(small_graph):
    g = small_graph
    rng = np.random.default_rng(0)
    row = np.repeat(np.arange(g.num_nodes), np.diff(g.indptr))
    ws = g.gcn_edge_weights(g.indices.astype(np.int64), row)
    ell = build_ell(g.indptr, g.indices, ws)
    h = rng.normal(size=(g.num_nodes, 50)).astype(np.float32)
    out = bucketed_spmm(ell, jnp.asarray(h))
    ref = degree_bucket_spmm_ref(jnp.asarray(g.indptr), jnp.asarray(g.indices),
                                 jnp.asarray(ws), jnp.asarray(h))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-4, atol=3e-4)


# ------------------------------------------------------ vectorized build_ell
@given(seed=st.integers(0, 200))
@settings(max_examples=12)
def test_build_ell_vectorized_matches_loop(seed):
    """The bulk-numpy builder reproduces the original per-node loop exactly:
    same bucketing, same heavy-row splitting, same row order, same padding."""
    indptr, indices, weights = _random_csr(seed)
    g_vec = build_ell(indptr, indices, weights, with_transpose=False)
    g_loop = _build_ell_loop(indptr, indices, weights)
    assert g_vec.num_rows == g_loop.num_rows
    for a, b in zip(g_vec.bucket_idx + g_vec.bucket_w + g_vec.bucket_rows,
                    g_loop.bucket_idx + g_loop.bucket_w + g_loop.bucket_rows):
        assert a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_build_ell_edgeless_graph():
    """A graph with zero edges builds (all-padding deg-0 rows) and SpMMs to 0,
    matching the loop builder."""
    n = 10
    indptr = np.zeros(n + 1, np.int64)
    g_vec = build_ell(indptr, np.zeros(0, np.int32), np.zeros(0, np.float32))
    g_loop = _build_ell_loop(indptr, np.zeros(0, np.int32),
                             np.zeros(0, np.float32))
    for a, b in zip(g_vec.bucket_idx + g_vec.bucket_w + g_vec.bucket_rows,
                    g_loop.bucket_idx + g_loop.bucket_w + g_loop.bucket_rows):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    out = bucketed_spmm(g_vec, jnp.ones((n, 8), jnp.float32))
    np.testing.assert_array_equal(np.asarray(out), np.zeros((n, 8)))


def test_build_ell_transpose_is_adjoint():
    """⟨A h, y⟩ == ⟨h, Aᵀ y⟩ with both sides computed by the kernel."""
    indptr, indices, weights = _random_csr(7)
    n = indptr.shape[0] - 1
    g = build_ell(indptr, indices, weights, block_rows=64)
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.normal(size=(n, 24)).astype(np.float32))
    y = jnp.asarray(rng.normal(size=(n, 24)).astype(np.float32))
    lhs = jnp.vdot(bucketed_spmm(g, h), y)
    rhs = jnp.vdot(h, bucketed_spmm(g.transpose, y))
    np.testing.assert_allclose(float(lhs), float(rhs), rtol=1e-4)


def test_ell_from_coo_fixed_capacity_shapes():
    """Two batches with the same (rows, E) envelope -> identical jit shapes."""
    rng = np.random.default_rng(0)
    n, e = 100, 400
    shapes = []
    for seed in range(2):
        r = np.random.default_rng(seed)
        g = ell_from_coo(r.integers(0, n, e), r.integers(0, n, e),
                         r.random(e).astype(np.float32), n)
        shapes.append(jax.tree.map(lambda x: x.shape, g))
    assert shapes[0] == shapes[1]


@given(seed=st.integers(0, 100))
@settings(max_examples=10)
def test_ell_from_coo_zero_degree_rows(seed):
    """Rows with no incoming edges emit an (empty) bucket-0 row rather than
    vanishing: they aggregate to exactly 0 and every other row matches the
    scatter-add oracle."""
    r = np.random.default_rng(seed)
    n, e = 64, 120
    src = r.integers(0, n, e)
    dst = r.integers(0, n // 2, e)   # rows [n/2, n) have zero in-degree
    w = r.random(e).astype(np.float32)
    g = ell_from_coo(src, dst, w, n)
    h = r.normal(size=(n, 16)).astype(np.float32)
    out = np.asarray(bucketed_spmm(g, jnp.asarray(h)))
    ref_out = np.zeros((n, 16), np.float32)
    np.add.at(ref_out, dst, w[:, None] * h[src])
    np.testing.assert_allclose(out, ref_out, rtol=2e-4, atol=1e-5)
    np.testing.assert_array_equal(out[n // 2:], 0.0)


def test_build_ell_exactly_at_capacity():
    """rows == capacity is legal: no padding rows, no error, exact results."""
    n = 8                             # 8 deg-[1..8] nodes -> 8 bucket-0 rows
    r = np.random.default_rng(0)
    deg = np.arange(1, n + 1)
    indptr = np.zeros(n + 1, np.int64)
    indptr[1:] = np.cumsum(deg)
    indices = r.integers(0, n, int(indptr[-1])).astype(np.int32)
    weights = r.random(int(indptr[-1])).astype(np.float32)
    g = build_ell(indptr, indices, weights, row_capacity=(8, 8, 8))
    assert g.bucket_idx[0].shape[0] == 8          # exactly full, zero pad rows
    h = r.normal(size=(n, 8)).astype(np.float32)
    out = np.asarray(bucketed_spmm(g, jnp.asarray(h)))
    ref_out = np.zeros((n, 8), np.float32)
    src = np.repeat(np.arange(n), deg)
    np.add.at(ref_out, src, weights[:, None] * h[indices])
    np.testing.assert_allclose(out, ref_out, rtol=2e-4, atol=1e-5)


def test_build_ell_overflow_raises_named_error():
    """One row over capacity raises ELLCapacityError (a ValueError, so legacy
    handlers keep working) instead of silently truncating edges."""
    n = 9                             # 9 deg-1 nodes -> 9 bucket-0 rows
    indptr = np.arange(n + 1, dtype=np.int64)
    indices = np.zeros(n, np.int32)
    weights = np.ones(n, np.float32)
    with pytest.raises(ELLCapacityError, match="bucket 0 .*9 rows exceed"):
        build_ell(indptr, indices, weights, row_capacity=(8, 8, 8))
    assert issubclass(ELLCapacityError, ValueError)


@given(seed=st.integers(0, 100))
@settings(max_examples=10)
def test_ell_from_coo_fixed_capacity_never_overflows(seed):
    """The worst-case capacities of ``fixed_capacity=True`` hold for any COO
    with the declared (rows, E) envelope — including heavy rows that split
    into many max-bucket chunks — and the aggregation stays exact."""
    r = np.random.default_rng(seed)
    n, e = 48, 600
    hub = int(r.integers(0, n))
    dst = np.where(r.random(e) < 0.5, hub, r.integers(0, n, e))  # heavy row
    src = r.integers(0, n, e)
    w = r.random(e).astype(np.float32)
    g = ell_from_coo(src, dst, w, n)   # must not raise ELLCapacityError
    h = r.normal(size=(n, 8)).astype(np.float32)
    out = np.asarray(bucketed_spmm(g, jnp.asarray(h)))
    ref_out = np.zeros((n, 8), np.float32)
    np.add.at(ref_out, dst, w[:, None] * h[src])
    np.testing.assert_allclose(out, ref_out, rtol=2e-4, atol=1e-5)


# ------------------------------------------------------------- gradient paths
def test_grad_bucketed_spmm_matches_oracle():
    """jax.grad through the kernel (custom VJP = transposed-graph SpMM)
    matches the jnp segment-sum oracle's gradient to 1e-5.

    Moderate degrees: at paper-scale degrees f32 summation-order noise alone
    exceeds 1e-5 (the adjoint property test above covers the heavy buckets).
    """
    indptr, indices, weights = _random_csr(3, heavy=False)
    n = indptr.shape[0] - 1
    g = build_ell(indptr, indices, weights, block_rows=64)
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.normal(size=(n, 20)).astype(np.float32))
    ptr, ind, w = (jnp.asarray(indptr), jnp.asarray(indices),
                   jnp.asarray(weights))
    f_k = lambda h_: jnp.sum(jnp.sin(bucketed_spmm(g, h_)))
    f_r = lambda h_: jnp.sum(jnp.sin(degree_bucket_spmm_ref(ptr, ind, w, h_)))
    np.testing.assert_allclose(float(f_k(h)), float(f_r(h)), rtol=1e-5)
    gk = jax.jit(jax.grad(f_k))(h)
    gr = jax.grad(f_r)(h)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(gr),
                               rtol=1e-5, atol=1e-5)


def test_vjp_bucketed_spmm_weight_cotangent():
    """The SpMM VJP also produces edge-weight cotangents matching the jnp
    ELL oracle (segment-backend parity: edge weights stay differentiable)."""
    indptr, indices, weights = _random_csr(11, heavy=False)
    n = indptr.shape[0] - 1
    g = build_ell(indptr, indices, weights, block_rows=64)
    rng = np.random.default_rng(2)
    h = jnp.asarray(rng.normal(size=(n, 16)).astype(np.float32))
    ct = jnp.asarray(rng.normal(size=(n, 16)).astype(np.float32))

    def oracle(ws, h_):   # pure-jnp replay of the bucketed kernel
        out = jnp.zeros((n + 1, 16), jnp.float32)
        for idx, w, rows in zip(g.bucket_idx, ws, g.bucket_rows):
            out = out.at[rows].add(ell_spmm_ref(idx, w, h_), mode="drop")
        return out[:n]

    _, vjp_k = jax.vjp(lambda ws, h_: bucketed_spmm(
        ELLGraph(g.bucket_idx, ws, g.bucket_rows, n, n, g.transpose), h_),
        g.bucket_w, h)
    _, vjp_r = jax.vjp(oracle, g.bucket_w, h)
    (dw_k, dh_k), (dw_r, dh_r) = vjp_k(ct), vjp_r(ct)
    np.testing.assert_allclose(np.asarray(dh_k), np.asarray(dh_r),
                               rtol=1e-5, atol=1e-5)
    for a, b, rows in zip(dw_k, dw_r, g.bucket_rows):
        real = np.asarray(rows) < n   # padding rows excluded: the oracle's
        np.testing.assert_allclose(    # scatter drops them, the VJP zeroes them
            np.asarray(a)[real], np.asarray(b)[real], rtol=1e-5, atol=1e-5)


def test_grad_lmc_compensate_matches_oracle():
    """Gradients w.r.t. store/beta/fresh/mask match the jnp oracle
    (including the scatter-add store cotangent), at unaligned shapes."""
    rng = np.random.default_rng(1)
    n, m, d = 70, 123, 50
    store = jnp.asarray(rng.normal(size=(m, d)).astype(np.float32))
    gids = jnp.asarray(rng.integers(0, m, n).astype(np.int32))
    beta = jnp.asarray(rng.random(n).astype(np.float32))
    mask = jnp.asarray((rng.random(n) > 0.2).astype(np.float32))
    fresh = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    out_k = lmc_compensate(store, gids, beta, fresh, mask)
    out_r = lmc_compensate_ref(store, gids, beta, fresh, mask)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=1e-6, atol=1e-6)
    f_k = lambda s, b, f, mk: jnp.sum(jnp.cos(lmc_compensate(s, gids, b, f, mk)))
    f_r = lambda s, b, f, mk: jnp.sum(jnp.cos(lmc_compensate_ref(s, gids, b, f, mk)))
    gk = jax.jit(jax.grad(f_k, argnums=(0, 1, 2, 3)))(store, beta, fresh, mask)
    gr = jax.grad(f_r, argnums=(0, 1, 2, 3))(store, beta, fresh, mask)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_grad_requires_transpose_graph():
    indptr, indices, weights = _random_csr(5)
    n = indptr.shape[0] - 1
    g = build_ell(indptr, indices, weights, with_transpose=False)
    h = jnp.ones((n, 8), jnp.float32)
    with pytest.raises(ValueError, match="with_transpose"):
        jax.grad(lambda h_: jnp.sum(bucketed_spmm(g, h_)))(h)


# --------------------------------------------------- compiled-path selection
def test_interpret_autodetect():
    """CPU containers fall back to interpret; TPU gets the compiled path."""
    assert default_interpret() == (jax.default_backend() != "tpu")
    # the default (interpret=None) must run on whatever backend this is
    rng = np.random.default_rng(0)
    idx = jnp.asarray(rng.integers(0, 16, (256, 8)).astype(np.int32))
    w = jnp.asarray(rng.random((256, 8)).astype(np.float32))
    h = jnp.asarray(rng.normal(size=(16, 128)).astype(np.float32))
    out = ell_spmm(idx, w, h)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ell_spmm_ref(idx, w, h)),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.slow
def test_ell_spmm_wide_bucket_sweep():
    """Full-width (K=128) bucket sweep — heavy in interpret mode."""
    rng = np.random.default_rng(0)
    for m, d in ((300, 128), (1000, 256)):
        idx = rng.integers(0, m, (256, 128)).astype(np.int32)
        w = (rng.random((256, 128)) * (rng.random((256, 128)) > 0.5)
             ).astype(np.float32)
        h = rng.normal(size=(m, d)).astype(np.float32)
        out = ell_spmm(jnp.asarray(idx), jnp.asarray(w), jnp.asarray(h))
        ref = ell_spmm_ref(jnp.asarray(idx), jnp.asarray(w), jnp.asarray(h))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)


def test_gnn_forward_with_kernel_aggregate(small_graph):
    """Swapping the jnp aggregation for the Pallas kernel is output-identical."""
    from repro.core import from_graph
    from repro.models import make_gnn
    g = small_graph
    data = from_graph(g)
    row = np.repeat(np.arange(g.num_nodes), np.diff(g.indptr))
    ws = g.gcn_edge_weights(g.indices.astype(np.int64), row)
    ell = build_ell(g.indptr, g.indices, ws)

    gnn_ref = make_gnn("gcn", g.feature_dim, 32, g.num_classes, 2)
    gnn_krn = make_gnn("gcn", g.feature_dim, 32, g.num_classes, 2,
                       aggregate=ell_aggregate_fn(ell))
    params = gnn_ref.init_params(jax.random.key(0))
    out_ref = gnn_ref.full_forward(params, data.x, data.edges, data.self_w)
    out_krn = gnn_krn.full_forward(params, data.x, data.edges, data.self_w)
    np.testing.assert_allclose(np.asarray(out_krn), np.asarray(out_ref),
                               rtol=2e-3, atol=2e-3)
