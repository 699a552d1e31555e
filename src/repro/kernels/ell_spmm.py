"""Blocked-ELL SpMM — the paper's aggregation hot-spot as a Pallas TPU kernel.

TPU adaptation of the CUDA scatter/gather SpMM the paper's PyG backend uses
(DESIGN.md §3): neighbor lists are padded to a per-bucket width K (powers of
two, host-side degree bucketing bounds the padding waste), giving a dense
(N, K) index/weight layout whose row tiles stream through VMEM; features are
blocked along D so a (block_rows, block_d) output tile accumulates K gathered
neighbor planes at a time.

Two gather strategies share the accumulation layout (vectorized — no per-row
scalar accumulation; broadcast multiply-add over the whole tile, f32
accumulator, full VPU lanes):

  * ``stream=True`` (default): the feature operand stays in **HBM**
    (``pl.ANY`` memory space) and the kernel body issues per-row
    HBM→VMEM ``pltpu.make_async_copy`` gathers, driven by the row tile's
    SMEM indices, into a 2-slot ``(block_rows, 128)`` VMEM scratch.
    Neighbor plane k+1's copies start before plane k's wait, so the DMA for
    k+1 overlaps the multiply-add for k (double buffering). No VMEM bound on
    the gather source M — this is what lets the compiled path gather from
    full-graph stores (the old resident block capped M at ~24k f32
    rows/device). Each copy moves one row's 128 lanes of the grid step's
    lane block, read through ``hbm_tiles``, the source's own HBM tile
    layout seen as (M/8, D/128, 8, 128): Mosaic refuses a (1, 128) window
    of a wider (M, D) row, and accepts the (1, 128) slice of one tile at
    every D that is a multiple of 128. So ``block_d`` is 128 here, and a
    D-wide layer takes D/128 copies per (row, neighbor) pair.
  * ``stream=False``: the legacy resident block — the whole ``(M, block_d)``
    feature slab rides in as one VMEM block and rows are copied out of it with
    dynamic slices. Cheaper for small sources revisited by many rows (one
    block load per feature tile instead of N·K row DMAs) but bounded by VMEM:
    forcing it with a source past ~12 MiB per block fails at Mosaic compile
    time on TPU.

``interpret=None`` / ``stream=None`` autodetect: compiled Mosaic on TPU,
interpreter fallback elsewhere (CPU containers cannot lower Mosaic kernels);
streaming everywhere (the interpreter emulates the DMA/semaphore protocol
exactly, so CPU CI verifies the streamed path — including at M well past the
old cap). Every VMEM block and scratch is (block_rows, 128) or
(block_rows, K) at any D: a D-wide operand is D/128 lane blocks of the grid,
never a wider tile.

Memory per grid step (defaults, streamed), at any D: 2-slot gather scratch
(2, 256, 128) f32 = 256 KiB, w tile (256, K≤128) = 128 KiB, out tile + f32
accumulator (256, 128) ×2 = 256 KiB of VMEM, and the row tile's
(block_rows, K) indices in SMEM, whose minor dim pads to 128 words: 128 KiB
per buffer. None of it grows with N, M or D: the index array reaches SMEM
one row tile at a time (the D/128 lane blocks of a row tile reuse it), where
a whole (N, K) operand would overflow v5e's 1 MiB of SMEM near N = 2k rows.

Mosaic cannot slice a lane at a dynamic offset, so weight column k of the
(block_rows, K) tile is read as a masked lane reduction (``_weight_column``)
rather than as ``w_ref[:, k]``; the reduction adds one nonzero to zeros, so
it is exact.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


LANES = 128   # a vreg's lanes: the width of one streamed row copy


def default_interpret() -> bool:
    """True when the Pallas kernels should run interpreted (no TPU present)."""
    return jax.default_backend() != "tpu"


def default_stream() -> bool:
    """True when the gather source should stream HBM→VMEM via per-row DMA.

    Streaming is the production default on every backend: it removes the
    resident-block VMEM cap on the gather source (full-graph historical
    stores compile), and the interpreter emulates the DMA protocol exactly so
    the same path is what CPU CI verifies. ``stream=False`` keeps the
    resident-block kernel for small sources and for streamed-vs-resident
    benchmarking.
    """
    return True


def hbm_tiles(h: jax.Array) -> jax.Array:
    """View a streamed gather source (M, D) as its own HBM tiles.

    XLA lays a 2-D TPU array out in (t, 128) tiles, t = 8 rows for 32-bit
    dtypes, row-major over tiles. Mosaic copies a (1, 128) row window out of
    an (M, D) operand only while D == 128; at D = 256 it refuses the DMA
    ("Slice shape along dimension 0 must be aligned to tiling (8)"), though
    the window is one contiguous run of a tile. The view (M/t, D/128, t,
    128) names the same bytes so that every row window is the (1, 128) slice
    of a 128-lane tile, which Mosaic accepts at any D; XLA lowers it to a
    bitcast. Only an M that is not a multiple of t pays a copy (the row pad).
    """
    m, d = h.shape
    t = 8 * 4 // h.dtype.itemsize
    if m % t:
        h = jnp.pad(h, ((0, t - m % t), (0, 0)))
    return h.reshape(-1, t, d // LANES, LANES).transpose(0, 2, 1, 3)


def tile_row(tiles_ref, j, lane):
    """The (1, 128) window of row j, lane block ``lane``, of an
    ``hbm_tiles`` view: the source of one row copy."""
    t = tiles_ref.shape[2]
    return tiles_ref.at[j // t, lane, pl.ds(j % t, 1), :]


def _weight_column(w_ref, k) -> jax.Array:
    """Column k of the (bn, K) weight tile as a (bn, 1) f32 lane-broadcast."""
    w = w_ref[:].astype(jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, w.shape, 1)
    return jnp.sum(jnp.where(lane == k, w, 0.0), axis=1, keepdims=True)


def _spmm_resident_kernel(idx_ref, w_ref, h_ref, o_ref, gath_ref, acc_ref, *,
                          K: int, block_rows: int):
    """Resident-block body: gather rows out of a full (M, block_d) VMEM slab.

    idx_ref: this row tile's (bn, K) int32 indices in SMEM; w_ref: (bn, K)
    VMEM tile; h_ref: (M, bd) VMEM feature block; gath_ref: (bn, bd) VMEM
    scratch; acc_ref: (bn, bd) f32 accumulator (full precision even for bf16
    inputs).
    """
    acc_ref[:] = jnp.zeros_like(acc_ref)

    def k_step(k, _):
        def gather_row(r, _):
            j = idx_ref[r, k]
            gath_ref[pl.ds(r, 1), :] = h_ref[pl.ds(j, 1), :]
            return 0

        jax.lax.fori_loop(0, block_rows, gather_row, 0)
        acc_ref[:] += (_weight_column(w_ref, k)
                       * gath_ref[:].astype(jnp.float32))
        return 0

    jax.lax.fori_loop(0, K, k_step, 0)
    o_ref[:] = acc_ref[:].astype(o_ref.dtype)


def _spmm_stream_kernel(idx_ref, w_ref, h_ref, o_ref, gath_ref, acc_ref,
                        sem_ref, *, K: int, block_rows: int):
    """Streaming body: per-row HBM→VMEM DMA gathers, double-buffered over k.

    h_ref is the feature operand's HBM tile view (``hbm_tiles``, memory
    space ``pl.ANY``); gath_ref is a (2, bn, 128) VMEM double buffer;
    sem_ref a (2,) DMA-semaphore array, one per slot. Neighbor plane k lands
    in slot k % 2: its copies are started one plane ahead (while plane k-1's
    multiply-add runs) and waited right before use. Every started copy is
    waited in the same grid step, so no DMA crosses grid-step boundaries.
    """
    lane = pl.program_id(1)

    def plane(k, slot, op):
        """start()/wait() the bn row-copies of neighbor plane k into slot."""
        def row(r, _):
            op(pltpu.make_async_copy(
                tile_row(h_ref, idx_ref[r, k], lane),
                gath_ref.at[slot, pl.ds(r, 1), :],
                sem_ref.at[slot]))
            return 0

        jax.lax.fori_loop(0, block_rows, row, 0)

    acc_ref[:] = jnp.zeros_like(acc_ref)
    plane(0, 0, lambda dma: dma.start())

    def k_step(k, _):
        slot = jax.lax.rem(k, 2)

        @pl.when(k + 1 < K)
        def _():  # overlap: plane k+1's DMA flies during plane k's compute
            plane(k + 1, jax.lax.rem(k + 1, 2), lambda dma: dma.start())

        plane(k, slot, lambda dma: dma.wait())
        acc_ref[:] += (_weight_column(w_ref, k)
                       * gath_ref[slot].astype(jnp.float32))
        return 0

    jax.lax.fori_loop(0, K, k_step, 0)
    o_ref[:] = acc_ref[:].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "block_d",
                                             "interpret", "stream"))
def ell_spmm(nbr_idx: jax.Array, nbr_w: jax.Array, h: jax.Array, *,
             block_rows: int = 256, block_d: int = 128,
             interpret: bool | None = None,
             stream: bool | None = None) -> jax.Array:
    """out[i] = Σ_k w[i,k] · h[idx[i,k]]  via pl.pallas_call.

    nbr_idx/nbr_w: (N, K); h: (M, D). N must divide by block_rows and D by
    block_d (the ops.py wrapper pads); streamed, block_d is 128 and D any
    multiple of it. ``interpret=None`` autodetects:
    compiled on TPU, interpreted elsewhere. ``stream=None`` autodetects to
    the HBM→VMEM DMA gather (no VMEM bound on M); ``stream=False`` forces the
    legacy resident ``(M, block_d)`` VMEM block (small sources only).
    """
    if interpret is None:
        interpret = default_interpret()
    if stream is None:
        stream = default_stream()
    n, k = nbr_idx.shape
    m, d = h.shape
    assert n % block_rows == 0 and d % block_d == 0, (n, d)
    grid = (n // block_rows, d // block_d)
    if stream:
        # one row copy is one 128-lane window of one HBM tile
        assert block_d == LANES, block_d
        kernel = functools.partial(_spmm_stream_kernel, K=k,
                                   block_rows=block_rows)
        h_spec = pl.BlockSpec(memory_space=pl.ANY)  # stays in HBM
        h = hbm_tiles(h)
        scratch = [pltpu.VMEM((2, block_rows, block_d), h.dtype),
                   pltpu.VMEM((block_rows, block_d), jnp.float32),
                   pltpu.SemaphoreType.DMA((2,))]
    else:
        kernel = functools.partial(_spmm_resident_kernel, K=k,
                                   block_rows=block_rows)
        # lint: ok(R003) legacy resident path: stream=True is the default and
        # Mosaic rejects >12 MiB blocks at compile time; the block is one
        # (M, block_d) lane block at any D, kept for small sources +
        # streamed-vs-resident benchmarking (module docstring)
        h_spec = pl.BlockSpec((m, block_d), lambda i, j: (0, j))
        scratch = [pltpu.VMEM((block_rows, block_d), h.dtype),
                   pltpu.VMEM((block_rows, block_d), jnp.float32)]
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            # SMEM: one (block_rows, K) index tile, its minor dim padded to
            # 128 words, so (256, 128) int32 = 128 KiB per buffer
            pl.BlockSpec((block_rows, k), lambda i, j: (i, 0),
                         memory_space=pltpu.SMEM),
            # lint: ok(R003) K <= 128 by bucket construction: build_ell caps
            # bucket widths at powers of two <= 128, so this w tile is at
            # most (256, 128) f32 = 128 KiB, whatever D is
            pl.BlockSpec((block_rows, k), lambda i, j: (i, 0)),
            h_spec,
        ],
        out_specs=pl.BlockSpec((block_rows, block_d), lambda i, j: (i, j)),
        scratch_shapes=scratch,
        out_shape=jax.ShapeDtypeStruct((n, d), h.dtype),
        interpret=interpret,
    )(nbr_idx, nbr_w, h)
