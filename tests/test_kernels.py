"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps,
gradient paths through the custom VJPs, and the vectorized ELL builder."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _prop import given, settings, strategies as st

from repro.kernels import (ELLCapacityError, build_ell,
                           bucketed_spmm, default_interpret, ell_from_coo,
                           ell_spmm, lmc_compensate)
from repro.kernels.ops import _build_ell_loop, fixed_row_capacity
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


def _all_planes(idx, block_rows=256):
    """Per-tile plane counts that read every plane of (N, K) ``idx``."""
    n, k = idx.shape
    return jnp.full((n // block_rows,), k, jnp.int32)


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
    out = ell_spmm(jnp.asarray(idx), jnp.asarray(w), jnp.asarray(h),
                   _all_planes(idx))
    ref = ell_spmm_ref(jnp.asarray(idx), jnp.asarray(w), jnp.asarray(h))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ell_spmm_bf16():
    rng = np.random.default_rng(0)
    idx = rng.integers(0, 64, (256, 8)).astype(np.int32)
    w = rng.random((256, 8)).astype(np.float32)
    h = rng.normal(size=(64, 128)).astype(jnp.bfloat16)
    out = ell_spmm(jnp.asarray(idx), jnp.asarray(w).astype(jnp.bfloat16),
                   jnp.asarray(h), _all_planes(idx))
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


@pytest.mark.parametrize("d", [256, 128], ids=["streamed", "d128"])
def test_lmc_compensate_across_id_blocks_and_feature_tiles(d):
    """Six row tiles whose ids span two 1024-word SMEM blocks: at d = 256
    (two feature tiles) the kernel's next-tile DMA crosses both a
    feature-tile and an id-block boundary; at d = 128 (one lane block)
    every next-tile DMA starts a new row tile."""
    rng = np.random.default_rng(0)
    n, m = 1300, 700
    args = [jnp.asarray(a) for a in (
        rng.normal(size=(m, d)).astype(np.float32),
        rng.integers(0, m, n).astype(np.int32),
        rng.random(n).astype(np.float32),
        rng.normal(size=(n, d)).astype(np.float32),
        (rng.random(n) > 0.2).astype(np.float32))]
    np.testing.assert_allclose(
        np.asarray(lmc_compensate(*args)),
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
def _bucket_arrays(g):
    return g.bucket_idx + g.bucket_w + g.bucket_rows + g.bucket_tile_k


@given(seed=st.integers(0, 200))
@settings(max_examples=12)
def test_build_ell_vectorized_matches_loop(seed):
    """The bulk-numpy builder reproduces the per-node loop exactly: same
    bucketing, same heavy-row splitting, same row order (longest first
    within a bucket), same padding, same per-tile plane counts."""
    indptr, indices, weights = _random_csr(seed)
    g_vec = build_ell(indptr, indices, weights, with_transpose=False)
    g_loop = _build_ell_loop(indptr, indices, weights)
    assert g_vec.num_rows == g_loop.num_rows
    for a, b in zip(_bucket_arrays(g_vec), _bucket_arrays(g_loop)):
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
    for a, b in zip(_bucket_arrays(g_vec), _bucket_arrays(g_loop)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert all(int(np.max(tk)) == 0 for tk in g_vec.bucket_tile_k)
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
    g = build_ell(indptr, indices, weights, block_rows=8,
                  row_capacity=(8, 8, 8))
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


def _hub_graph(seed, directed):
    """Random graph with hub nodes of degree above the last bucket (128), so
    their rows split into chunks. ``directed`` gives in-hubs (long CSR rows)
    and out-hubs (named in many rows) on different nodes, so A and Aᵀ have
    different degree profiles; otherwise the graph is symmetric."""
    from repro.graph import Graph
    r = np.random.default_rng(seed)
    n = int(r.integers(800, 1200))
    deg = r.integers(0, 21, n)
    hubs = r.choice(n, 20, replace=False)
    deg[hubs[:4]] = r.integers(400, 700, 4)          # in-hubs
    src = np.repeat(np.arange(n), deg)
    dst = np.where(r.random(src.shape[0]) < 0.45,    # out-hubs
                   r.choice(hubs[4:], src.shape[0]),
                   r.integers(0, n, src.shape[0]))
    x = np.zeros((n, 4), np.float32)
    y = np.zeros(n, np.int32)
    mask = np.ones(n, bool)
    if not directed:
        return Graph.from_edges(n, src, dst, x, y, mask, mask, mask)
    keep = src != dst
    src, dst = src[keep], dst[keep]
    indptr = np.zeros(n + 1, np.int64)
    indptr[1:] = np.cumsum(np.bincount(src, minlength=n))
    return Graph(indptr=indptr, indices=dst.astype(np.int32), x=x, y=y,
                 train_mask=mask, val_mask=mask, test_mask=mask)


@pytest.mark.parametrize("include_halo", [True, False],
                         ids=["halo", "no-halo"])
@pytest.mark.parametrize("directed", [False, True],
                         ids=["symmetric", "directed"])
@given(seed=st.integers(0, 10_000))
@settings(max_examples=8)
def test_profile_capacity_never_overflows_sampler_batches(seed, directed,
                                                          include_halo):
    """The degree-profile capacities hold every batch a ClusterSampler
    builds, in both directions of the ELL, on graphs whose hubs split into
    several max-bucket chunks, and never exceed the worst case. Built as
    ``host_batch`` builds it, and again at 8-row blocks, where the rounding
    leaves the bounds almost no room."""
    from repro.core import host_batch
    from repro.graph import ClusterSampler
    g = _hub_graph(seed, directed)
    assert g.degrees().max() > 128
    parts = np.random.default_rng(seed).permutation(g.num_nodes) % 6
    s = ClusterSampler(g, 6, 4, parts=parts, seed=seed,
                       include_halo=include_halo)
    rows = s.pad_batch + s.pad_halo
    caps = fixed_row_capacity(rows, s.pad_edges, degree_hists=s.degree_hists)
    worst = fixed_row_capacity(rows, s.pad_edges)
    assert all(c <= w for c, w in zip(caps, worst))
    split = 0
    for _ in range(3):
        for sg in s.epoch():
            ell = host_batch(sg, backend="ell").ell   # raises on overflow
            for g_dir in (ell, ell.transpose):
                assert tuple(r.shape[0] for r in g_dir.bucket_rows) == caps
            ne = sg.n_edges_real
            tight = ell_from_coo(sg.edge_src[:ne], sg.edge_dst[:ne],
                                 sg.edge_w[:ne], sg.n_ext, block_rows=8,
                                 edge_capacity=s.pad_edges,
                                 degree_hists=s.degree_hists, as_jax=False)
            for g_dir in (tight, tight.transpose):
                used = np.concatenate(g_dir.bucket_rows)
                used = used[used < sg.n_ext]
                split += used.size - np.unique(used).size
    assert split > 0            # some hub row split into several chunks


@pytest.mark.parametrize("backend", ["ell", "ti"])
def test_profile_capacity_shapes_are_fixed_per_sampler(small_graph,
                                                       small_parts, backend):
    """Every batch of one sampler's epoch has identical ELL shapes (one jit
    trace), at the profile capacities and at most the worst case."""
    from repro.core import host_batch
    from repro.graph import ClusterSampler
    s = ClusterSampler(small_graph, 16, 4, parts=small_parts, seed=11)
    rows = s.pad_batch + s.pad_halo
    caps = fixed_row_capacity(rows, s.pad_edges, degree_hists=s.degree_hists)
    worst = fixed_row_capacity(rows, s.pad_edges)
    assert all(c <= w for c, w in zip(caps, worst)) and caps != worst
    shapes = set()
    for sg in s.epoch():
        ell = host_batch(sg, backend=backend).ell
        shapes.add(jax.tree.map(lambda a: a.shape, ell))
        assert tuple(r.shape[0] for r in ell.bucket_rows) == caps
    assert len(shapes) == 1


def test_coo_without_profile_keeps_worst_case_shapes(small_graph,
                                                     small_parts):
    """Without degree histograms (a hand-built COO, or a subgraph that
    carries none) the bucket shapes stay fixed_row_capacity's worst case."""
    from repro.core import host_batch
    from repro.graph import ClusterSampler
    r = np.random.default_rng(0)
    n, e = 100, 400
    g = ell_from_coo(r.integers(0, n, e), r.integers(0, n, e),
                     r.random(e).astype(np.float32), n)
    assert tuple(x.shape[0] for x in g.bucket_rows) == fixed_row_capacity(n, e)
    s = ClusterSampler(small_graph, 16, 4, parts=small_parts, seed=12)
    sg = dataclasses.replace(s.sample(), degree_hists=None)
    ell = host_batch(sg, backend="ell").ell
    worst = fixed_row_capacity(sg.n_ext, s.pad_edges)
    for g_dir in (ell, ell.transpose):
        assert tuple(x.shape[0] for x in g_dir.bucket_rows) == worst


def test_real_edge_ell_is_bit_exact_against_padded_worst_case(small_graph,
                                                              small_parts):
    """The ELL of a batch's real edges at the profile capacities gives the
    same SpMM and VJP, to the last bit, as the same batch built with every
    padded edge at the worst-case capacities: each real row sums the same
    products in the same slot order, and the padded edges only added
    w = 0 products to local row 0 (kernel interpreted on the CPU).

    Row 0 of Aᵀ led with the padded entries in the padded build (they sort
    first, dst 0), so its real entries sat at slot offset P mod 128, P the
    padded edge count. Bit equality there needs them in one 128-slot chunk
    of that build, as they are in this batch (asserted); across a chunk
    boundary that build split row 0's sum in two partial rows."""
    from repro.core import host_batch
    from repro.graph import ClusterSampler
    s = ClusterSampler(small_graph, 16, 4, parts=small_parts, seed=7)
    sg = s.sample()
    ne, n = sg.n_edges_real, sg.n_ext
    src, dst = sg.edge_src[:ne], sg.edge_dst[:ne]
    pad = sg.edge_src.shape[0] - ne
    assert pad > 0
    assert pad % 128 + int((src == 0).sum()) <= 128
    new = jax.device_put(host_batch(sg, backend="ell").ell)
    old = ell_from_coo(sg.edge_src, sg.edge_dst, sg.edge_w, n)
    assert (sum(x.size for x in new.bucket_idx)
            < sum(x.size for x in old.bucket_idx))
    # local row 0 holds only its real degree, in both directions
    for g_dir, deg0 in ((new, int((dst == 0).sum())),
                        (new.transpose, int((src == 0).sum()))):
        held = sum(int((np.asarray(w)[np.asarray(rows) == 0] != 0).sum())
                   for w, rows in zip(g_dir.bucket_w, g_dir.bucket_rows))
        n_rows = sum(int((np.asarray(rows) == 0).sum())
                     for rows in g_dir.bucket_rows)
        assert deg0 > 0 and held == deg0 and n_rows == 1
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.normal(size=(n, 24)).astype(np.float32))
    ct = jnp.asarray(rng.normal(size=(n, 24)).astype(np.float32))
    outs = []
    for g_ell in (new, old):
        out, vjp = jax.vjp(lambda h_, g_=g_ell: bucketed_spmm(g_, h_), h)
        outs.append((np.asarray(out), np.asarray(vjp(ct)[0])))
    np.testing.assert_array_equal(outs[0][0], outs[1][0])
    np.testing.assert_array_equal(outs[0][1], outs[1][1])


# ------------------------------------------------- per-tile plane counts
# plane counts of four 64-row tiles: an empty tile, a full one, two between
TILE_COUNTS = [(0, 8, 3, 1), (8, 0, 0, 5), (2, 7, 8, 0)]


@pytest.mark.parametrize("d", [128, 256])
@pytest.mark.parametrize("counts", TILE_COUNTS, ids=lambda c: "-".join(
    map(str, c)))
def test_ell_spmm_stops_each_tile_at_its_plane_count(counts, d):
    """Tile i reads planes k < tile_k[i] only. Rows hold real lengths up to
    their tile's count, one row at it, and w == 0 past their length, so the
    first plane past the count is padding in every row; the planes past it
    carry garbage indices and weights the kernel must not read. Small
    integer values keep every sum exact, so the kernel equals the oracle on
    the padded (idx, w) to the bit, and an all-empty tile gives exact 0."""
    rng = np.random.default_rng(sum(counts) + d)
    k, bn, m = 8, 64, 300
    counts = np.asarray(counts, np.int32)
    n = bn * counts.size
    length = np.minimum(rng.integers(0, k + 1, n), np.repeat(counts, bn))
    length[::bn] = counts                          # one row at the count
    slot = np.arange(k)[None, :]
    idx = rng.integers(0, m, (n, k)).astype(np.int32)
    w = rng.integers(-4, 5, (n, k)).astype(np.float32)
    h = rng.integers(-8, 9, (m, d)).astype(np.float32)
    real = slot < length[:, None]
    w_pad = np.where(real, w, 0.0).astype(np.float32)
    idx_pad = np.where(real, idx, 0).astype(np.int32)
    # past the tile's count the kernel reads nothing: garbage there is inert
    past = slot >= np.repeat(counts, bn)[:, None]
    w_run = np.where(past, w, w_pad).astype(np.float32)
    out = np.asarray(ell_spmm(jnp.asarray(idx_pad), jnp.asarray(w_run),
                              jnp.asarray(h), jnp.asarray(counts),
                              block_rows=bn))
    ref = np.asarray(ell_spmm_ref(jnp.asarray(idx_pad), jnp.asarray(w_pad),
                                  jnp.asarray(h)))
    np.testing.assert_array_equal(out, ref)
    for i in np.flatnonzero(counts == 0):
        np.testing.assert_array_equal(out[i * bn:(i + 1) * bn], 0.0)


def _node_order_full_planes(g):
    """The same ELL with each bucket's rows back in node order (pad rows
    last) and every tile at K planes: the layout before rows were ordered
    by length and tiles counted."""
    def one(g_dir):
        perm = [np.argsort(np.asarray(rows), kind="stable")
                for rows in g_dir.bucket_rows]
        return dataclasses.replace(
            g_dir,
            bucket_idx=tuple(jnp.asarray(np.asarray(a)[p])
                             for a, p in zip(g_dir.bucket_idx, perm)),
            bucket_w=tuple(jnp.asarray(np.asarray(a)[p])
                           for a, p in zip(g_dir.bucket_w, perm)),
            bucket_rows=tuple(jnp.asarray(np.asarray(a)[p])
                              for a, p in zip(g_dir.bucket_rows, perm)),
            bucket_tile_k=tuple(jnp.full(tk.shape, idx.shape[1], jnp.int32)
                                for tk, idx in zip(g_dir.bucket_tile_k,
                                                   g_dir.bucket_idx)),
            transpose=None)
    return dataclasses.replace(one(g), transpose=one(g.transpose))


def test_length_ordered_counted_ell_is_bit_exact_against_node_order():
    """``bucketed_spmm`` and its ``jax.grad`` over the length-ordered ELL
    with per-tile plane counts equal, to the last bit, the same graph laid
    out in node order with every tile at K planes: each row sums the same
    products in the same order, and only w == 0 planes were dropped. The
    graph has deg-0 rows and a bucket of mixed lengths, both ways."""
    r = np.random.default_rng(5)
    n = 300
    deg = r.choice([0, 1, 3, 8, 9, 17, 32], size=n)
    indptr = np.zeros(n + 1, np.int64)
    indptr[1:] = np.cumsum(deg)
    indices = r.integers(0, n, int(indptr[-1])).astype(np.int32)
    weights = r.random(int(indptr[-1])).astype(np.float32)
    g = build_ell(indptr, indices, weights, block_rows=32)
    plain = _node_order_full_planes(g)
    assert np.asarray(g.bucket_tile_k[0]).min() == 0    # empty tiles
    for g_dir in (g, g.transpose):
        counts = np.asarray(g_dir.bucket_tile_k[1])     # K = 32, mixed
        assert 0 < counts.min() < counts.max() <= 32
    h = jnp.asarray(r.normal(size=(n, 40)).astype(np.float32))
    loss = lambda g_, h_: jnp.sum(jnp.sin(bucketed_spmm(g_, h_)))
    for f in (lambda g_: bucketed_spmm(g_, h),
              lambda g_: jax.grad(loss, argnums=1)(g_, h)):
        np.testing.assert_array_equal(np.asarray(f(g)), np.asarray(f(plain)))


def _check_length_order(g_dir, nnz):
    """Rows of every bucket hold their real edges as a prefix, longest row
    first, and each tile's plane count is its longest real row."""
    held = 0
    for w, tk in zip(g_dir.bucket_w, g_dir.bucket_tile_k):
        w, tk = np.asarray(w), np.asarray(tk)
        real = w != 0
        length = real.sum(axis=1)
        held += int(length.sum())
        np.testing.assert_array_equal(
            real, np.arange(w.shape[1])[None, :] < length[:, None])
        assert np.all(np.diff(length) <= 0)
        bn = w.shape[0] // tk.shape[0]
        assert tk.shape[0] * bn == w.shape[0]
        np.testing.assert_array_equal(tk, length.reshape(-1, bn).max(axis=1))
    assert held == nnz


@given(seed=st.integers(0, 200))
@settings(max_examples=10)
def test_build_ell_orders_rows_by_length_and_counts_tiles(seed):
    """Both directions of ``build_ell`` on graphs with deg-0 and heavy
    (split) rows: non-increasing row lengths in every bucket, and
    ``bucket_tile_k`` equal to each tile's longest real row."""
    indptr, indices, weights = _random_csr(seed)
    weights = weights + 0.5                  # every real edge reads w != 0
    g = build_ell(indptr, indices, weights, block_rows=16)
    for g_dir in (g, g.transpose):
        _check_length_order(g_dir, indices.shape[0])


def test_ell_from_coo_profile_capacities_order_rows_by_length(small_graph,
                                                              small_parts):
    """The same holds for sampler batches built by ``ell_from_coo`` at the
    degree-profile capacities, both directions, at two tile heights."""
    from repro.core import host_batch
    from repro.graph import ClusterSampler
    s = ClusterSampler(small_graph, 16, 4, parts=small_parts, seed=13)
    for sg in list(s.epoch())[:2]:
        ne = sg.n_edges_real
        assert np.all(sg.edge_w[:ne] != 0)
        for ell in (host_batch(sg, backend="ell").ell,
                    ell_from_coo(sg.edge_src[:ne], sg.edge_dst[:ne],
                                 sg.edge_w[:ne], sg.n_ext, block_rows=8,
                                 edge_capacity=s.pad_edges,
                                 degree_hists=s.degree_hists, as_jax=False)):
            for g_dir in (ell, ell.transpose):
                _check_length_order(g_dir, ne)


@pytest.mark.parametrize("seed", [3, 11])
def test_issued_copies_lie_between_real_pairs_and_slots(seed):
    """``ELLGraph.issued_copies`` covers every real pair and stops short of
    the slots once tiles stop at their longest row; the same graph in node
    order at K planes a tile issues a copy for every slot."""
    indptr, indices, weights = _random_csr(seed)
    g = build_ell(indptr, indices, weights, block_rows=16)
    plain = _node_order_full_planes(g)
    for g_dir, p_dir in ((g, plain), (g.transpose, plain.transpose)):
        slots = sum(idx.size for idx in g_dir.bucket_idx)
        assert indices.shape[0] <= g_dir.issued_copies() < slots
        assert p_dir.issued_copies() == slots


def test_build_ell_capacity_must_be_whole_tiles():
    """A row capacity that is not a multiple of the tile height cannot be
    cut into tiles, so it is refused before any row is laid out."""
    indptr = np.arange(5, dtype=np.int64)
    with pytest.raises(ValueError, match="not a multiple of block_rows 8"):
        build_ell(indptr, np.zeros(4, np.int32), np.ones(4, np.float32),
                  block_rows=8, row_capacity=(12, 8, 8))


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
        dataclasses.replace(g, bucket_w=ws), h_), g.bucket_w, h)
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
    out = ell_spmm(idx, w, h, _all_planes(idx))
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
        out = ell_spmm(jnp.asarray(idx), jnp.asarray(w), jnp.asarray(h),
                       _all_planes(idx))
        ref = ell_spmm_ref(jnp.asarray(idx), jnp.asarray(w), jnp.asarray(h))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)


def test_gnn_forward_with_kernel_aggregate(small_graph):
    """``aux.ell`` set to the whole graph's ELL routes every layer's
    aggregation through the Pallas kernel, output-identical to the
    segment sum over the COO edges (``aux.ell=None``), for GCN and GCNII."""
    from repro.core import from_graph
    from repro.models import make_gnn
    from repro.models.gnn import LayerAux
    g = small_graph
    data = from_graph(g)
    row = np.repeat(np.arange(g.num_nodes), np.diff(g.indptr))
    ws = g.gcn_edge_weights(g.indices.astype(np.int64), row)
    ell = build_ell(g.indptr, g.indices, ws)
    for arch in ("gcn", "gcnii"):
        gnn = make_gnn(arch, g.feature_dim, 32, g.num_classes, 2)
        params = gnn.init_params(jax.random.key(0))
        h0 = gnn.embed_apply(params["embed"], data.x)
        aux_seg = LayerAux(edges=data.edges, x=data.x, h0=h0,
                           self_w=data.self_w)
        aux_krn = aux_seg._replace(ell=ell)
        lp0 = gnn.layer_params(params, 0)
        for aux, kernel in ((aux_seg, False), (aux_krn, True)):
            jaxpr = jax.make_jaxpr(
                lambda h, a=aux: gnn.layer_apply(lp0, 0, h, a))(h0)
            assert ("pallas_call" in str(jaxpr)) == kernel, arch
        h_seg = h_krn = h0
        for l in range(gnn.num_layers):
            lp = gnn.layer_params(params, l)
            h_seg = gnn.layer_apply(lp, l, h_seg, aux_seg)
            h_krn = gnn.layer_apply(lp, l, h_krn, aux_krn)
            np.testing.assert_allclose(np.asarray(h_krn), np.asarray(h_seg),
                                       rtol=2e-3, atol=2e-3,
                                       err_msg=f"{arch} layer {l}")
