"""jit'd, differentiable production wrappers around the Pallas kernels.

`bucketed_spmm` is the deployable aggregation: rows are degree-bucketed host
side (powers of two) so ELL padding waste stays < 2x, each bucket runs one
`ell_spmm` pallas_call, and the results scatter-add back in row order. Within
a bucket the builder orders rows longest first and records each row tile's
longest real row (``ELLGraph.bucket_tile_k``), so the kernel stops each tile
at that plane and issues no copy for the slots past it. It is a
`jax.custom_vjp`: the transpose of an ELL SpMM is an SpMM over the transposed
adjacency, so `build_ell` also buckets Aᵀ and the backward pass runs through
the same kernel (this is what lets `core/lmc.py`'s per-layer ``jax.vjp`` calls
stay on the kernel path — DESIGN.md §3).

`lmc_compensate` is the differentiable, shape-padding entry point for the
fused gather+lerp compensation kernel (Eq. 9/12); its VJP scatters the store
cotangent and keeps β/mask/fresh gradients exact against the jnp oracle.

`build_ell` / `ell_from_coo` are bulk-numpy preprocessors (degree bucketing
via repeat/searchsorted, heavy-row splitting via chunk index arithmetic — no
per-node Python loop); `ell_from_coo` additionally fixes per-bucket row
capacities (`fixed_row_capacity`) from the padded batch sizes and the
graph's degree histograms, so every mini-batch of a sampler traces to the
same jit shapes, each bucket no larger than the graph's rows can fill. It
takes a batch's real edges only: padded edges would take slots and add 0.
The train step puts the SpMM on its hot path with ``make_train_step(...,
backend="ell")``, which hands each layer the batch's ``ELLGraph``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.compensate import lmc_compensate_kernel
from repro.kernels.ell_spmm import default_interpret, ell_spmm
from repro.kernels import ref


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


class ELLCapacityError(ValueError):
    """A bucket's real row count exceeds its fixed padded capacity.

    Raised by the host-side builders (``build_ell``/``ell_from_coo``) when
    ``row_capacity`` is given and a degree bucket would need more rows than
    the fixed shape allows — the alternative, silent truncation, would drop
    edges and corrupt aggregations. Catch it to rebuild with larger
    capacities (or let ``fixed_capacity=True`` derive them,
    ``fixed_row_capacity``).
    """


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class ELLGraph:
    """Degree-bucketed padded-ELL adjacency (host-built, device arrays).

    Registered as a pytree so it can ride through ``jit`` (as part of a Batch)
    and through ``jax.custom_vjp``: the index/weight/row/tile arrays are
    children, the row/col counts are static aux data, and ``transpose`` (the
    bucketed Aᵀ, used by the SpMM VJP) is a nested child.

    A bucket's rows come longest first. ``bucket_tile_k[b][i]`` is the
    longest real row of the bucket's row tile i, the planes the kernel
    reads there; the tile height is ``tile_rows(b)``.
    """
    bucket_idx: tuple      # per bucket: (rows_b, K_b) int32 neighbor ids
    bucket_w: tuple        # per bucket: (rows_b, K_b) f32 weights
    bucket_rows: tuple     # per bucket: (rows_b,) int32 destination rows
    bucket_tile_k: tuple   # per bucket: (rows_b // tile,) int32 plane counts
    num_rows: int          # output rows (static)
    num_cols: int          # gather-source rows, == h.shape[0] (static)
    transpose: Optional["ELLGraph"] = None

    def tree_flatten(self):
        return ((self.bucket_idx, self.bucket_w, self.bucket_rows,
                 self.bucket_tile_k, self.transpose),
                (self.num_rows, self.num_cols))

    @classmethod
    def tree_unflatten(cls, aux, children):
        idx, w, rows, tile_k, t = children
        return cls(bucket_idx=idx, bucket_w=w, bucket_rows=rows,
                   bucket_tile_k=tile_k, num_rows=aux[0], num_cols=aux[1],
                   transpose=t)

    def tile_rows(self, b: int) -> int:
        """Bucket b's row-tile height: its rows over its plane counts."""
        return self.bucket_idx[b].shape[0] // self.bucket_tile_k[b].shape[0]

    def issued_copies(self) -> int:
        """The row copies the kernel issues per lane block over this
        direction's buckets: Σ ``bucket_tile_k`` × tile height (host
        arrays)."""
        return sum(int(np.sum(tk)) * self.tile_rows(b)
                   for b, tk in enumerate(self.bucket_tile_k))


# --------------------------------------------------------------- host builders
def _ell_buckets(indptr: np.ndarray, indices: np.ndarray, weights: np.ndarray,
                 buckets: Sequence[int], block_rows: int,
                 row_capacity: Optional[Sequence[int]], as_jax: bool = True):
    """CSR -> per-bucket (idx, w, rows, tile_k) arrays, fully vectorized.

    Reproduces the rows of the per-node loop (``_build_ell_loop``) exactly:
    each chunk of ≤ kmax neighbors lands in the smallest bucket that fits
    it; deg-0 nodes emit one empty bucket-0 row. Within a bucket rows come
    in non-increasing order of real length, ties in (node, chunk) order, so
    empty and pad rows trail; ``tile_k`` holds each ``block_rows`` tile's
    longest real row. A given ``row_capacity`` must be a multiple of
    ``block_rows``. ``as_jax=False`` keeps the bucket arrays as host numpy
    (the prefetch pipeline builds batches off-thread and lets the consumer
    ``device_put``).
    """
    n = indptr.shape[0] - 1
    deg = np.diff(indptr).astype(np.int64)
    kmax = int(buckets[-1])

    # one row per kmax-chunk of each neighbor list (deg-0 nodes get one chunk)
    nchunks = np.maximum((deg + kmax - 1) // kmax, 1)
    row_node = np.repeat(np.arange(n, dtype=np.int64), nchunks)
    first = np.zeros(n, np.int64)
    first[1:] = np.cumsum(nchunks)[:-1]
    chunk_start = (np.arange(row_node.shape[0], dtype=np.int64)
                   - np.repeat(first, nchunks)) * kmax
    chunk_len = np.clip(deg[row_node] - chunk_start, 0, kmax)
    bucket_of = np.searchsorted(np.asarray(buckets, np.int64), chunk_len)

    b_idx, b_w, b_rows, b_tile_k = [], [], [], []
    for b, k in enumerate(buckets):
        sel = np.flatnonzero(bucket_of == b)   # in (node, chunk) order
        # longest first, ties in (node, chunk) order; on a narrow unsigned
        # key numpy's stable sort is a radix sort, not a merge sort
        key = (kmax - chunk_len[sel]).astype(np.min_scalar_type(kmax))
        sel = sel[np.argsort(key, kind="stable")]
        rows = sel.shape[0]
        if row_capacity is not None:
            rows_pad = int(row_capacity[b])
            if rows > rows_pad:
                raise ELLCapacityError(
                    f"bucket {b} (K={k}): {rows} rows exceed capacity {rows_pad}")
            if rows_pad % block_rows:
                raise ValueError(f"bucket {b} (K={k}): capacity {rows_pad} "
                                 f"is not a multiple of block_rows "
                                 f"{block_rows}")
        else:
            rows_pad = max(_round_up(rows, block_rows), block_rows)
        idx = np.zeros((rows_pad, k), np.int32)
        w = np.zeros((rows_pad, k), np.float32)
        rid = np.full((rows_pad,), n, np.int32)  # pad rows -> dropped
        if rows:
            if indices.shape[0]:
                base = indptr[row_node[sel]] + chunk_start[sel]
                offs = np.arange(k, dtype=np.int64)
                valid = offs[None, :] < chunk_len[sel][:, None]
                pos = np.where(valid, base[:, None] + offs[None, :], 0)
                idx[:rows] = np.where(valid, indices[pos], 0).astype(np.int32)
                w[:rows] = np.where(valid, weights[pos], 0.0).astype(np.float32)
            # else: edgeless graph — every row is an all-padding deg-0 row
            rid[:rows] = row_node[sel].astype(np.int32)
        length = np.zeros((rows_pad,), np.int32)
        length[:rows] = chunk_len[sel]
        conv = jnp.asarray if as_jax else (lambda a: a)
        b_idx.append(conv(idx))
        b_w.append(conv(w))
        b_rows.append(conv(rid))
        b_tile_k.append(conv(length.reshape(-1, block_rows).max(axis=1)))
    return tuple(b_idx), tuple(b_w), tuple(b_rows), tuple(b_tile_k)


def _build_ell_loop(indptr, indices, weights, buckets=(8, 32, 128),
                    block_rows: int = 256):
    """Original per-node Python-loop builder, rows ordered longest first
    within each bucket (a stable sort of the (node, chunk) order).

    Kept only as the correctness reference for the vectorized `build_ell`
    (property-tested against it) and as the baseline of the preprocessing
    benchmark; O(n) interpreted Python — do not use on large graphs.
    """
    n = indptr.shape[0] - 1
    kmax = buckets[-1]
    b_idx, b_w, b_rows, b_tile_k = [], [], [], []
    row_ids = [[] for _ in buckets]
    row_idx = [[] for _ in buckets]
    row_ws = [[] for _ in buckets]
    row_len = [[] for _ in buckets]

    for v in range(n):
        lo, hi = indptr[v], indptr[v + 1]
        nbrs, ws = indices[lo:hi], weights[lo:hi]
        for s in range(0, max(len(nbrs), 1), kmax):
            part_n = nbrs[s:s + kmax]
            part_w = ws[s:s + kmax]
            b = next(i for i, k in enumerate(buckets) if len(part_n) <= k)
            k = buckets[b]
            pad = k - len(part_n)
            row_ids[b].append(v)
            row_idx[b].append(np.pad(part_n.astype(np.int32), (0, pad)))
            row_ws[b].append(np.pad(part_w.astype(np.float32), (0, pad)))
            row_len[b].append(len(part_n))

    for b, k in enumerate(buckets):
        rows = len(row_ids[b])
        rows_pad = max(_round_up(rows, block_rows), block_rows)
        order = sorted(range(rows), key=lambda i: -row_len[b][i])
        idx = np.zeros((rows_pad, k), np.int32)
        w = np.zeros((rows_pad, k), np.float32)
        rid = np.full((rows_pad,), n, np.int32)
        lens = [row_len[b][i] for i in order] + [0] * (rows_pad - rows)
        if rows:
            idx[:rows] = np.stack([row_idx[b][i] for i in order])
            w[:rows] = np.stack([row_ws[b][i] for i in order])
            rid[:rows] = np.asarray([row_ids[b][i] for i in order], np.int32)
        b_idx.append(jnp.asarray(idx))
        b_w.append(jnp.asarray(w))
        b_rows.append(jnp.asarray(rid))
        b_tile_k.append(jnp.asarray(
            [max(lens[t:t + block_rows]) for t in range(0, rows_pad,
                                                         block_rows)],
            jnp.int32))
    return ELLGraph(tuple(b_idx), tuple(b_w), tuple(b_rows), tuple(b_tile_k),
                    num_rows=n, num_cols=n)


def _transpose_csr(indptr, indices, weights, num_cols):
    """CSR of A -> CSR of Aᵀ (bulk numpy: one argsort over the edge list)."""
    n = indptr.shape[0] - 1
    rows = np.repeat(np.arange(n, dtype=np.int64), np.diff(indptr))
    order = np.argsort(indices, kind="stable")
    counts = np.bincount(indices, minlength=num_cols)
    t_indptr = np.zeros(num_cols + 1, np.int64)
    t_indptr[1:] = np.cumsum(counts)
    return t_indptr, rows[order].astype(np.int32), \
        np.asarray(weights)[order].astype(np.float32)


def build_ell(indptr: np.ndarray, indices: np.ndarray, weights: np.ndarray,
              buckets=(8, 32, 128), block_rows: int = 256, *,
              num_cols: Optional[int] = None,
              row_capacity: Optional[Sequence[int]] = None,
              with_transpose: bool = True, as_jax: bool = True) -> ELLGraph:
    """CSR -> degree-bucketed ELL (bulk numpy, no per-node Python loop).

    Rows with deg > max(buckets) are split into multiple partial rows (their
    partial sums add via the final scatter-add, keeping K bounded). Each
    bucket's rows come longest first, and ``bucket_tile_k`` gives each
    ``block_rows`` tile its longest real row: the kernel's tile height and
    the neighbour planes it reads there (the row order is free, since
    ``bucket_rows`` maps every ELL row to its destination). When
    ``with_transpose`` the transposed adjacency is bucketed too, giving the
    SpMM its custom-VJP backward graph. ``row_capacity`` (per-bucket padded
    row counts, applied to both directions, each a multiple of
    ``block_rows``) fixes the array shapes so every batch of a sampler hits
    one jit trace.
    """
    indptr = np.asarray(indptr, np.int64)
    indices = np.asarray(indices)
    weights = np.asarray(weights)
    n = indptr.shape[0] - 1
    num_cols = n if num_cols is None else int(num_cols)

    fwd = _ell_buckets(indptr, indices, weights, buckets, block_rows,
                       row_capacity, as_jax)
    t = None
    if with_transpose:
        t_ptr, t_ind, t_w = _transpose_csr(indptr, indices, weights, num_cols)
        t = ELLGraph(*_ell_buckets(t_ptr, t_ind, t_w, buckets, block_rows,
                                   row_capacity, as_jax),
                     num_rows=num_cols, num_cols=n)
    return ELLGraph(*fwd, num_rows=n, num_cols=num_cols, transpose=t)


def _profile_rows(hist: np.ndarray, buckets: Sequence[int]) -> list:
    """Most rows each bucket can take from a graph with degree histogram
    ``hist`` (entry d: the nodes of degree d), over any set of rows whose
    degrees are at most their nodes': a node puts its one remainder chunk
    in bucket b > 0 only where its degree exceeds K_{b-1}, and at most
    ceil(deg / K_max) chunks in the last bucket. Bucket 0 is not bounded
    here (deg-0 pad rows emit a row there)."""
    deg = np.arange(hist.shape[0], dtype=np.int64)
    hist = np.asarray(hist, np.int64)
    kmax = int(buckets[-1])
    rows = [None]
    for b in range(1, len(buckets)):
        over = deg > int(buckets[b - 1])
        per_node = (-(-deg // kmax) if b == len(buckets) - 1
                    else np.ones_like(deg))
        rows.append(int((hist * per_node)[over].sum()))
    return rows


def fixed_row_capacity(num_rows: int, num_edges: int, buckets=(8, 32, 128),
                       block_rows: int = 256, *,
                       degree_hists: Optional[Sequence] = None) -> tuple:
    """Per-bucket padded row counts, fixed for every batch of a sampler.

    The worst case for any graph with ≤ num_edges edges over num_rows rows:
    each row emits ≤ 1 remainder chunk (any bucket) plus full-kmax chunks
    (last bucket only, ≤ E/kmax in total). With ``degree_hists`` (the
    graph's degree histograms, ``Graph.degree_hists``; one per direction
    of the ELL, as ``build_ell`` applies one capacity to A and Aᵀ) each
    bucket past the first is capped by what rows of those degrees can put
    there (``_profile_rows``, the most over the histograms), rounded up to
    ``block_rows``: never above the worst case, and sound for any batch
    whose rows' degrees are at most their nodes', which a sampled subgraph
    of the graph's real edges is.
    """
    caps = [max(_round_up(max(num_rows, 1), block_rows), block_rows)
            for _ in buckets]
    caps[-1] = max(_round_up(max(num_rows, 1) + num_edges // int(buckets[-1]),
                             block_rows), block_rows)
    if degree_hists is not None:
        bounds = [_profile_rows(h, buckets) for h in degree_hists]
        for b in range(1, len(buckets)):
            rows = max(bound[b] for bound in bounds)
            caps[b] = min(caps[b], max(_round_up(rows, block_rows),
                                       block_rows))
    return tuple(caps)


def ell_from_coo(src: np.ndarray, dst: np.ndarray, w: np.ndarray,
                 num_rows: int, *, buckets=(8, 32, 128),
                 block_rows: int = 256, fixed_capacity: bool = True,
                 edge_capacity: Optional[int] = None,
                 degree_hists: Optional[Sequence] = None,
                 as_jax: bool = True) -> ELLGraph:
    """Local COO (a PaddedSubgraph's real edges) -> square ELLGraph.

    Aggregation semantics match ``models.gnn.segment_spmm``: out[dst] +=
    w·h[src]. Pass only the real edges: a padded edge (w == 0, row 0)
    adds nothing to a sum but takes ELL slots, and the profile capacities
    leave no room for them. With ``fixed_capacity`` the bucket shapes
    depend only on (num_rows, ``edge_capacity``, ``degree_hists``, buckets,
    block_rows) (``fixed_row_capacity``), so all batches of a sampler share
    one jit trace; ``edge_capacity`` is the padded edge count (default: the
    edges given). ``as_jax=False`` leaves the bucket arrays on the host
    (numpy) for deferred ``jax.device_put``.
    """
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    w = np.asarray(w, np.float32)
    order = np.argsort(dst, kind="stable")
    counts = np.bincount(dst, minlength=num_rows)
    indptr = np.zeros(num_rows + 1, np.int64)
    indptr[1:] = np.cumsum(counts)
    if edge_capacity is None:
        edge_capacity = src.shape[0]
    caps = (fixed_row_capacity(num_rows, int(edge_capacity), buckets,
                               block_rows, degree_hists=degree_hists)
            if fixed_capacity else None)
    return build_ell(indptr, src[order], w[order], buckets, block_rows,
                     num_cols=num_rows, row_capacity=caps, as_jax=as_jax)


# ------------------------------------------------------------ kernel wrappers
def _bucketed_spmm_impl(g: ELLGraph, h: jax.Array,
                        interpret: bool) -> jax.Array:
    """out[i] = Σ_{j in N(i)} w_ij h[j] over all degree buckets; each
    bucket's kernel runs at the graph's tile height and plane counts."""
    n = g.num_rows
    d = h.shape[1]
    d_pad = _round_up(d, 128)
    hp = jnp.pad(h, ((0, 0), (0, d_pad - d))) if d_pad != d else h
    out = jnp.zeros((n + 1, d_pad), h.dtype)   # row n catches padding rows
    for b, (idx, w, rows, tile_k) in enumerate(zip(
            g.bucket_idx, g.bucket_w, g.bucket_rows, g.bucket_tile_k)):
        part = ell_spmm(idx, w, hp, tile_k, block_rows=g.tile_rows(b),
                        interpret=interpret)
        out = out.at[rows].add(part.astype(h.dtype), mode="drop")
    return out[:n, :d]


def _zeros_cotangent(tree):
    """Zero cotangents for a pytree with integer leaves (float0 for ints)."""
    def z(x):
        x = jnp.asarray(x)
        if jnp.issubdtype(x.dtype, jnp.integer) or x.dtype == jnp.bool_:
            return np.zeros(x.shape, jax.dtypes.float0)
        return jnp.zeros_like(x)
    return jax.tree.map(z, tree)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _bucketed_spmm_vjp(interpret: bool, g: ELLGraph, h: jax.Array):
    return _bucketed_spmm_impl(g, h, interpret)


def _bucketed_spmm_fwd(interpret, g, h):
    return _bucketed_spmm_impl(g, h, interpret), (g, h)


def _bucketed_spmm_bwd(interpret, res, ct):
    g, h = res
    if g.transpose is None:
        raise ValueError(
            "bucketed_spmm: gradient requested but the ELLGraph was built "
            "with with_transpose=False; the SpMM VJP needs the bucketed Aᵀ")
    # the backward SpMM is the same kernel over Aᵀ: its gather source is the
    # cotangent, which is full-graph-sized whenever the forward output was
    dh = _bucketed_spmm_impl(g.transpose, ct, interpret)
    # weight cotangent dw[i,k] = ⟨ct[rows[i]], h[idx[i,k]]⟩ (jnp gather; XLA
    # DCEs it under jit when the caller only differentiates w.r.t. h, the
    # LMC train-step case). Row `num_rows` of the padded ct zeroes the
    # all-padding rows (rid == num_rows).
    ctp = jnp.pad(ct, ((0, 1), (0, 0)))
    dws = tuple(
        jnp.einsum("rd,rkd->rk", jnp.take(ctp, rows, axis=0, mode="clip"),
                   jnp.take(h, idx, axis=0, mode="clip")).astype(w.dtype)
        for idx, w, rows in zip(g.bucket_idx, g.bucket_w, g.bucket_rows))
    dg = dataclasses.replace(_zeros_cotangent(g), bucket_w=dws)
    return dg, dh


_bucketed_spmm_vjp.defvjp(_bucketed_spmm_fwd, _bucketed_spmm_bwd)


def bucketed_spmm(g: ELLGraph, h: jax.Array, *,
                  interpret: bool | None = None) -> jax.Array:
    """Differentiable bucketed ELL SpMM: out = A h.

    VJP: dh = Aᵀ(dout) through the transposed-bucket kernel (the same HBM
    gather as the forward, so nothing bounds a full-graph-sized cotangent);
    d(bucket_w) via jnp gathers (padding slots get the would-be-edge
    gradient ct·h[0], which is meaningless but never read back — ELL weights
    map to CSR entries only where the builder placed real edges).
    """
    if interpret is None:
        interpret = default_interpret()
    return _bucketed_spmm_vjp(bool(interpret), g, h)


def _compensate_impl(store, gids, beta, fresh, mask, interpret):
    n, d = fresh.shape
    d_pad = _round_up(d, 128)
    block = 256 if n >= 256 else _round_up(max(n, 8), 8)
    n_pad = _round_up(n, block)
    sp = jnp.pad(store, ((0, 0), (0, d_pad - d))) if d_pad != d else store
    fp = fresh
    if d_pad != d or n_pad != n:
        fp = jnp.pad(fresh, ((0, n_pad - n), (0, d_pad - d)))
    pad1 = ((0, n_pad - n),)
    gp = jnp.pad(gids, pad1) if n_pad != n else gids
    bp = jnp.pad(beta, pad1) if n_pad != n else beta
    mp = jnp.pad(mask, pad1) if n_pad != n else mask
    out = lmc_compensate_kernel(sp, gp, bp, fp, mp, block_rows=block,
                                interpret=interpret)
    return out[:n, :d]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _lmc_compensate_vjp(interpret, store, gids, beta, fresh, mask):
    return _compensate_impl(store, gids, beta, fresh, mask, interpret)


def _compensate_fwd(interpret, store, gids, beta, fresh, mask):
    out = _compensate_impl(store, gids, beta, fresh, mask, interpret)
    return out, (store, gids, beta, fresh, mask)


def _compensate_bwd(interpret, res, ct):
    # The adjoint is VMEM-cap-free at any store size: the hist gather and
    # the d_store scatter-add lower to XLA gather/scatter over HBM operands
    store, gids, beta, fresh, mask = res
    hist = jnp.take(store, gids, axis=0, mode="clip")
    d_store = jnp.zeros_like(store).at[gids].add(
        ((mask * (1.0 - beta))[:, None] * ct).astype(store.dtype))
    d_beta = jnp.sum(ct * mask[:, None] * (fresh - hist),
                     axis=-1).astype(beta.dtype)
    d_fresh = (ct * (mask * beta)[:, None]).astype(fresh.dtype)
    d_mask = jnp.sum(ct * ((1.0 - beta)[:, None] * hist
                           + beta[:, None] * fresh), axis=-1).astype(mask.dtype)
    d_gids = np.zeros(gids.shape, jax.dtypes.float0)
    return d_store, d_gids, d_beta, d_fresh, d_mask


_lmc_compensate_vjp.defvjp(_compensate_fwd, _compensate_bwd)


def lmc_compensate(store: jax.Array, gids: jax.Array, beta: jax.Array,
                   fresh: jax.Array, mask: jax.Array, *,
                   interpret: bool | None = None) -> jax.Array:
    """ĥ = mask · [(1-β)·store[gid] + β·fresh]  (Eq. 9/12), differentiable.

    store (M, D); gids/beta/mask (N,); fresh (N, D) -> (N, D). Arbitrary N/D
    (padded internally to kernel tiles); VJP is exact against the jnp oracle,
    including the scatter-add store cotangent (an XLA HBM scatter — no
    resident VMEM block, so the backward pass is cap-free at any M).

    The *full-graph* historical store stays in HBM and only the gathered
    rows cross into VMEM, so the compiled path has no bound on the store
    row count.

    Perf note: when D is not a multiple of 128 the *whole store* is padded to
    the tile width on every call — keep hidden dims 128-aligned in production
    (the pad is then a no-op).
    """
    if interpret is None:
        interpret = default_interpret()
    return _lmc_compensate_vjp(bool(interpret), store, gids, beta, fresh,
                               mask)


__all__ = ["ELLGraph", "build_ell", "ell_from_coo", "fixed_row_capacity",
           "bucketed_spmm", "ell_spmm", "lmc_compensate", "default_interpret",
           "ref"]
