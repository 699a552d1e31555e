"""Pallas TPU kernels for the paper's compute hot-spots.

  ell_spmm.py    — blocked-ELL SpMM (the GNN aggregation the paper's CUDA
                   backend implements with scatter/gather); vectorized tile
                   kernel with per-row-tile SMEM index tiles; ref:
                   ref.ell_spmm_ref
  compensate.py  — fused gather + convex-combination for LMC Eq. (9)/(12)
  ops.py         — differentiable jit wrappers: degree-bucketed production
                   SpMM + compensate with custom VJPs (transpose-graph
                   backward), bulk-numpy ELL builders, AggregateFn
  ref.py         — pure-jnp oracles

Kernels are written for TPU (pl.pallas_call with SMEM index tiles and
8x128-aligned VMEM tiles). ``interpret`` autodetects per backend: compiled
Mosaic on TPU, interpreter fallback on CPU containers. ``stream``
autodetects to the HBM→VMEM double-buffered DMA gather (per-row
``pltpu.make_async_copy`` into a 2-slot VMEM scratch), which removes any VMEM
bound on the gather source — full-graph historical stores compile
(DESIGN.md §3).
"""
from repro.kernels.ops import (ELLCapacityError, ELLGraph, build_ell,
                               bucketed_spmm, default_interpret,
                               default_stream, ell_aggregate_fn, ell_from_coo,
                               ell_spmm, fixed_row_capacity, lmc_compensate)
from repro.kernels import ref

__all__ = ["ELLCapacityError", "ELLGraph", "build_ell", "ell_from_coo",
           "fixed_row_capacity", "bucketed_spmm", "ell_spmm", "lmc_compensate",
           "ell_aggregate_fn", "default_interpret", "default_stream", "ref"]
