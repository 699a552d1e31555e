"""HBM→VMEM streamed-gather coverage: the DMA double-buffered kernels (fwd +
custom-VJP bwd) must match the jnp oracles at gather-source sizes well past
the ~24k-row cap a VMEM-resident source block would have. CPU CI exercises
the exact DMA/semaphore protocol through the Pallas interpreter;
tests/test_tpu_compile.py compiles the same kernels for a described TPU
v5e."""
import jax
import jax.numpy as jnp
import numpy as np
from _prop import given, settings, strategies as st

from repro.kernels import build_ell, bucketed_spmm, lmc_compensate
from repro.kernels.ref import degree_bucket_spmm_ref, lmc_compensate_ref

# a VMEM-resident source block would cap the gather source at ~24k f32
# rows/device (12 MiB / 128 lanes / 4 bytes); the kernels must clear 4x that
OLD_CAP_ROWS = 12 * 2**20 // (128 * 4)
BIG_M = 4 * OLD_CAP_ROWS + 1536


def _rect_csr(seed, n_rows, num_cols, max_deg=20):
    """Random rectangular CSR: n_rows rows gathering from num_cols sources."""
    r = np.random.default_rng(seed)
    deg = r.integers(0, max_deg, n_rows)
    indptr = np.zeros(n_rows + 1, np.int64)
    indptr[1:] = np.cumsum(deg)
    nnz = int(indptr[-1])
    indices = r.integers(0, num_cols, nnz).astype(np.int32)
    weights = r.random(nnz).astype(np.float32)
    return indptr, indices, weights


@given(seed=st.integers(0, 100), m_extra=st.sampled_from([0, 2560, 65536]))
@settings(max_examples=4)
def test_streamed_spmm_beyond_cap_fwd_and_grad(seed, m_extra):
    """bucketed_spmm (fwd + custom-VJP grad) vs the segment-sum oracle with a
    gather source ≥ 4x the old resident-block cap."""
    m = BIG_M + m_extra
    assert m >= 4 * OLD_CAP_ROWS
    n_rows = 150
    indptr, indices, ws = _rect_csr(seed, n_rows, m)
    g = build_ell(indptr, indices, ws, num_cols=m, block_rows=64)
    rng = np.random.default_rng(seed + 1)
    h = jnp.asarray(rng.normal(size=(m, 128)).astype(np.float32))
    ptr, ind, w = (jnp.asarray(indptr), jnp.asarray(indices), jnp.asarray(ws))

    f_k = lambda h_: jnp.sum(jnp.sin(bucketed_spmm(g, h_)))
    f_r = lambda h_: jnp.sum(jnp.sin(
        degree_bucket_spmm_ref(ptr, ind, w, h_)[:n_rows]))
    out = bucketed_spmm(g, h)
    ref = degree_bucket_spmm_ref(ptr, ind, w, h)[:n_rows]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    # the bwd SpMM streams over the bucketed Aᵀ whose *output* is full-graph
    # sized — the dh it produces covers all m source rows
    gk = jax.jit(jax.grad(f_k))(h)
    gr = jax.grad(f_r)(h)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(gr),
                               rtol=1e-5, atol=1e-5)


@given(seed=st.integers(0, 100), beta_max=st.floats(0.1, 1.0))
@settings(max_examples=4)
def test_streamed_compensate_beyond_cap_fwd_and_grad(seed, beta_max):
    """lmc_compensate (fwd + custom-VJP grads incl. the scatter-add store
    cotangent) vs the jnp oracle with a store ≥ 4x the old cap, at unaligned
    N/D (the ops wrapper pads to kernel tiles)."""
    m = BIG_M
    rng = np.random.default_rng(seed)
    n, d = 300, 50
    store = jnp.asarray(rng.normal(size=(m, d)).astype(np.float32))
    gids = jnp.asarray(rng.integers(0, m, n).astype(np.int32))
    beta = jnp.asarray((rng.random(n) * beta_max).astype(np.float32))
    mask = jnp.asarray((rng.random(n) > 0.2).astype(np.float32))
    fresh = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    out_k = lmc_compensate(store, gids, beta, fresh, mask)
    out_r = lmc_compensate_ref(store, gids, beta, fresh, mask)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=1e-5, atol=1e-5)
    f_k = lambda s, b, f, mk: jnp.sum(jnp.cos(
        lmc_compensate(s, gids, b, f, mk)))
    f_r = lambda s, b, f, mk: jnp.sum(jnp.cos(
        lmc_compensate_ref(s, gids, b, f, mk)))
    gk = jax.jit(jax.grad(f_k, argnums=(0, 1, 2, 3)))(store, beta, fresh, mask)
    gr = jax.grad(f_r, argnums=(0, 1, 2, 3))(store, beta, fresh, mask)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)
