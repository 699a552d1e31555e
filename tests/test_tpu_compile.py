"""The Pallas kernels compile for a TPU v5e at real sizes — without a chip.

Interpret mode (every other kernel test) runs any block size and any slice,
so it cannot see what Mosaic refuses: lane slices at dynamic offsets, more
SMEM or VMEM than a kernel may use. These tests compile ahead of time for a
*described* v5e (``jax.experimental.topologies``), from shapes only, at the
``gas-arxiv`` batch sizes the training path really sees, at one lane block
(128) and at the published width (256), where each row copy reads a
128-lane window of the source's HBM tiles.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports every test file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.graph.synthetic import DATASET_PRESETS
from repro.kernels import ELLGraph, bucketed_spmm, lmc_compensate
from repro.kernels.ops import fixed_row_capacity

# ClusterSampler on the gcn-arxiv benchmark graph, 80 parts, 40 clusters per
# batch: padded batch + halo rows and padded edges of one mini-batch
ARXIV_ROWS = 86_720 + 169_344
ARXIV_EDGES = 2_317_568
ARXIV_NODES = DATASET_PRESETS["arxiv-like"][0]
BUCKETS = (8, 32, 128)
# the bucket capacities a gas-arxiv batch has, sized from the graph's degree
# profile (fixed_row_capacity(..., degree_hists=)), against the worst case
ARXIV_PROFILE_CAPS = (256_256, 157_184, 256)
TILE = 256       # the builder's row tile: one plane count per tile


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler plug-in here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


@pytest.fixture(params=[128, 256], ids=lambda d: f"hidden{d}")
def hidden(request) -> int:
    """One lane block, and GAS/LMC's arxiv width (two)."""
    return request.param


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _ell_shapes(sharding, rows: int, edges: int, caps=None) -> ELLGraph:
    """Shapes of the square bucketed ELL (and its transpose) for one batch,
    at the worst-case bucket capacities unless ``caps`` are given, with one
    scalar-prefetched plane count per 256-row tile of each bucket."""
    if caps is None:
        caps = fixed_row_capacity(rows, edges, BUCKETS)

    def one():
        return (tuple(_shape(sharding, (c, k), jnp.int32)
                      for c, k in zip(caps, BUCKETS)),
                tuple(_shape(sharding, (c, k)) for c, k in zip(caps, BUCKETS)),
                tuple(_shape(sharding, (c,), jnp.int32) for c in caps),
                tuple(_shape(sharding, (c // TILE,), jnp.int32) for c in caps))

    return ELLGraph(*one(), rows, rows, transpose=ELLGraph(*one(), rows, rows))


def _kernel_calls(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def _leading_operands(compiled) -> list:
    """The first operand's layout of every kernel call: a scalar-prefetch
    operand leads the list."""
    return [m.group(1) for m in re.finditer(
        r'custom_call_target="tpu_custom_call", '
        r"operand_layout_constraints=\{([^,]*),", compiled.as_text())]


@pytest.mark.parametrize("caps", [None, ARXIV_PROFILE_CAPS],
                         ids=["streamed", "profile"])
def test_bucketed_spmm_fwd_bwd_compiles(one_chip, caps, hidden):
    """Forward and custom-VJP backward: one kernel per bucket each way
    (value_and_grad keeps the forward live; grad alone would drop it), at
    the worst-case and at the degree-profile bucket capacities, each kernel
    taking its bucket's per-tile plane counts as a scalar-prefetch
    operand."""
    g = _ell_shapes(one_chip, ARXIV_ROWS, ARXIV_EDGES, caps)
    h = _shape(one_chip, (ARXIV_ROWS, hidden))

    def loss(h_, g_):
        return jnp.sum(bucketed_spmm(g_, h_, interpret=False))

    compiled = jax.jit(jax.value_and_grad(loss)).lower(h, g).compile()
    assert _kernel_calls(compiled) == 2 * len(BUCKETS)
    if caps is None:
        caps = fixed_row_capacity(ARXIV_ROWS, ARXIV_EDGES, BUCKETS)
    assert sorted(_leading_operands(compiled)) == sorted(
        f"s32[{c // TILE}]{{0}}" for c in caps * 2)


@pytest.mark.parametrize("m", [ARXIV_NODES], ids=["streamed"])
def test_lmc_compensate_fwd_bwd_compiles(one_chip, m, hidden):
    """The fused gather + lerp over the full-graph store (``m`` rows, read
    by DMA from HBM), and its VJP."""
    n = ARXIV_ROWS - 86_720            # halo rows
    vec = _shape(one_chip, (n,))
    args = (_shape(one_chip, (m, hidden)), vec, _shape(one_chip, (n, hidden)),
            vec, _shape(one_chip, (n,), jnp.int32))

    def loss(store, beta, fresh, mask, gids):
        return jnp.sum(lmc_compensate(store, gids, beta, fresh, mask,
                                      interpret=False))

    compiled = jax.jit(
        jax.value_and_grad(loss, argnums=(0, 1, 2, 3))).lower(*args).compile()
    assert _kernel_calls(compiled) == 1


@pytest.mark.parametrize("preset", sorted(DATASET_PRESETS))
def test_compensate_compiles_for_every_preset(one_chip, preset, hidden):
    """SMEM holds one row tile of gather ids, not all of them: a halo as
    large as the whole graph compiles for every preset (reddit-like's 233k
    rows overflowed SMEM when the ids were one whole-array operand)."""
    m = DATASET_PRESETS[preset][0]
    n = -(-m // 256) * 256
    vec = _shape(one_chip, (n,))
    compiled = jax.jit(
        lambda s, g_, b, f, mk: lmc_compensate(s, g_, b, f, mk,
                                               interpret=False)).lower(
        _shape(one_chip, (m, hidden)), _shape(one_chip, (n,), jnp.int32),
        vec, _shape(one_chip, (n, hidden)), vec).compile()
    assert _kernel_calls(compiled) == 1
