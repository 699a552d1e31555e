"""Fused LMC compensation kernel — Eq. (9)/(12)'s gather + convex-combine.

The per-halo-node update  ĥ_i = m_i·[(1-β_i)·H̄[gid_i] + β_i·h̃_i]  is a gather
from the (node-sharded) historical store fused with the lerp and validity
mask, so the historical row never round-trips through HBM twice.

Kernel layout mirrors ell_spmm.py: each grid step's gather ids ride into SMEM
as one (block_rows,) block, so SMEM use does not grow with N (a whole-array
SMEM operand overflowed v5e's 1 MiB near 262k rows), and the lerp+mask runs
as one broadcast multiply-add over the whole tile (β and mask arrive as
(N, 1) lane-broadcast columns).

The store stays in **HBM** (``pl.ANY``) and each grid step's
(block_rows, 128) gather arrives via per-row HBM→VMEM
``pltpu.make_async_copy`` into a 2-slot VMEM scratch. A copy reads one
row's 128 lanes through ``ell_spmm.hbm_tiles``, the store's own HBM tile
layout, which Mosaic accepts at every D that is a multiple of 128 (a
(1, 128) window of a wider (M, D) row it refuses), so a D-wide store takes
D/128 lane blocks of the grid. The pipeline runs across grid steps: step
t's compute overlaps step t+1's DMA (slot t % 2 computes while slot
(t+1) % 2 fills). The store is *full-graph* on the LMC train path, and
nothing here bounds its row count M.

``interpret=None`` autodetects like ell_spmm (the interpreter emulates the
DMA/semaphore protocol exactly, so CPU CI verifies the same kernel). This
module exposes the shape-aligned raw kernel call; the padded,
differentiable production entry point is ``ops.lmc_compensate``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ell_spmm import (LANES, default_interpret, hbm_tiles,
                                    tile_row)

# XLA tiles a 1-D int32 array in 1024-word chunks, and Mosaic accepts a 1-D
# block only if it matches: an SMEM id block is a whole number of chunks
_SMEM_CHUNK = 1024


def _smem_id_blocks(ids: jax.Array, tile: int):
    """Lay 1-D ids out for one `tile`-id SMEM slice per row tile.

    Returns ``(ids, block, spec)``: ``ids`` zero-padded to whole 1024-word
    blocks, each holding one or more whole tiles, and ``spec(shift)`` — the
    SMEM BlockSpec whose grid step (i, j) holds row tile ``i + shift``'s ids
    (clamped to the last block) at offset ``(i + shift) · tile % block``.
    """
    n = ids.shape[0]
    if n == tile:          # one row tile, of any height
        block = _SMEM_CHUNK * -(-tile // _SMEM_CHUNK)
    else:
        assert _SMEM_CHUNK % tile == 0, (tile, n)
        block = _SMEM_CHUNK
    padded = block * -(-n // block)
    if padded != n:
        ids = jnp.pad(ids, (0, padded - n))
    last = padded // block - 1

    def spec(shift: int = 0) -> pl.BlockSpec:
        return pl.BlockSpec(
            (block,), lambda i, j: (jnp.minimum((i + shift) * tile // block,
                                                last),),
            memory_space=pltpu.SMEM)

    return ids, block, spec


def _comp_stream_kernel(gid_ref, gid_next_ref, beta_ref, mask_ref, fresh_ref,
                        store_ref, o_ref, gath_ref, sem_ref, *,
                        block_rows: int, grid_j: int, gid_block: int):
    """Kernel body, pipelined across row/feature tiles.

    store_ref is the store's HBM tile view (``hbm_tiles``, ``pl.ANY``), so
    each row copy is one 128-lane window of one tile at any D; gath_ref is
    a (2, bn, 128) VMEM double buffer; sem_ref a (2,) DMA-semaphore array.
    Grid steps run sequentially on a TPU core and scratch persists across
    them, so tile t = i·J + j computes out of slot t % 2 while tile t+1's
    row copies fill slot (t+1) % 2 — the gather DMA for the next tile
    overlaps this tile's lerp. Tile 0 pays the only un-overlapped gather (warm-up). gid_ref holds
    row tile i's ids in SMEM and gid_next_ref row tile i+1's, which tile t+1
    reads when it starts a new row tile (``_smem_id_blocks``).
    """
    i, j = pl.program_id(0), pl.program_id(1)
    t = i * grid_j + j
    num_t = pl.num_programs(0) * grid_j
    base = i * block_rows % gid_block
    next_base = (i + 1) * block_rows % gid_block

    def tile(col_tile, slot, op, ids_ref, ids_base):
        """start()/wait() the bn row-copies of one grid tile into slot."""
        def row(r, _):
            op(pltpu.make_async_copy(
                tile_row(store_ref, ids_ref[ids_base + r], col_tile),
                gath_ref.at[slot, pl.ds(r, 1), :],
                sem_ref.at[slot]))
            return 0

        jax.lax.fori_loop(0, block_rows, row, 0)

    @pl.when(t == 0)
    def _():  # warm-up: the first tile's gather cannot overlap anything
        tile(0, 0, lambda dma: dma.start(), gid_ref, base)

    # overlap: next tile's DMA flies during this tile's lerp
    next_slot = jax.lax.rem(t + 1, 2)

    @pl.when(j + 1 < grid_j)
    def _():  # next tile: same row tile, next feature tile
        tile(j + 1, next_slot, lambda dma: dma.start(), gid_ref, base)

    @pl.when(jnp.logical_and(j + 1 == grid_j, t + 1 < num_t))
    def _():  # next tile: first feature tile of the next row tile
        tile(0, next_slot, lambda dma: dma.start(), gid_next_ref, next_base)

    slot = jax.lax.rem(t, 2)
    tile(j, slot, lambda dma: dma.wait(), gid_ref, base)
    b = beta_ref[:]          # (bn, 1) broadcast over lanes
    hist = gath_ref[slot].astype(fresh_ref.dtype)
    o_ref[:] = mask_ref[:] * ((1.0 - b) * hist + b * fresh_ref[:])


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def lmc_compensate_kernel(store: jax.Array, gids: jax.Array, beta: jax.Array,
                          fresh: jax.Array, mask: jax.Array, *,
                          block_rows: int = 256,
                          interpret: bool | None = None) -> jax.Array:
    """store (M, D); gids/beta/mask (N,); fresh (N, D) -> (N, D).

    Requires N % block_rows == 0 and D % 128 == 0 (``ops.lmc_compensate``
    pads and adds the custom VJP). Nothing bounds the store row count M.
    """
    if interpret is None:
        interpret = default_interpret()
    n, d = fresh.shape
    assert n % block_rows == 0 and d % LANES == 0, (n, d)
    grid = (n // block_rows, d // LANES)
    beta2 = beta.reshape(n, 1).astype(fresh.dtype)
    mask2 = mask.reshape(n, 1).astype(fresh.dtype)
    # each grid step's row tile of gather ids rides in an SMEM block
    gids, gid_block, gid_spec = _smem_id_blocks(gids, block_rows)
    return pl.pallas_call(
        functools.partial(_comp_stream_kernel, block_rows=block_rows,
                          grid_j=grid[1], gid_block=gid_block),
        grid=grid,
        in_specs=[gid_spec(), gid_spec(1),
                  pl.BlockSpec((block_rows, 1), lambda i, j: (i, 0)),
                  pl.BlockSpec((block_rows, 1), lambda i, j: (i, 0)),
                  pl.BlockSpec((block_rows, LANES), lambda i, j: (i, j)),
                  pl.BlockSpec(memory_space=pl.ANY)],  # store stays in HBM
        out_specs=pl.BlockSpec((block_rows, LANES), lambda i, j: (i, j)),
        # DMA is byte-exact: the double buffer must carry the store dtype
        scratch_shapes=[pltpu.VMEM((2, block_rows, LANES), store.dtype),
                        pltpu.SemaphoreType.DMA((2,))],
        out_shape=jax.ShapeDtypeStruct((n, d), fresh.dtype),
        interpret=interpret,
    )(gids, gids, beta2, mask2, fresh, hbm_tiles(store))
