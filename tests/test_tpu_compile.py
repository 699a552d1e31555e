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
# the resident gather block holds the whole (M, 128) f32 source in VMEM:
# 8k rows (4 MiB, double-buffered) is a size it is kept for
RESIDENT_ROWS = 8192


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


def _ell_shapes(sharding, rows: int, edges: int) -> ELLGraph:
    """Shapes of the square bucketed ELL (and its transpose) for one batch."""
    caps = fixed_row_capacity(rows, edges, BUCKETS)

    def one():
        return (tuple(_shape(sharding, (c, k), jnp.int32)
                      for c, k in zip(caps, BUCKETS)),
                tuple(_shape(sharding, (c, k)) for c, k in zip(caps, BUCKETS)),
                tuple(_shape(sharding, (c,), jnp.int32) for c in caps))

    return ELLGraph(*one(), rows, rows, transpose=ELLGraph(*one(), rows, rows))


def _kernel_calls(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


@pytest.mark.parametrize("stream", [True, False], ids=["streamed", "resident"])
def test_bucketed_spmm_fwd_bwd_compiles(one_chip, stream, hidden):
    """Forward and custom-VJP backward: one kernel per bucket each way
    (value_and_grad keeps the forward live; grad alone would drop it)."""
    rows = ARXIV_ROWS if stream else RESIDENT_ROWS
    edges = ARXIV_EDGES if stream else RESIDENT_ROWS * 16
    g = _ell_shapes(one_chip, rows, edges)
    h = _shape(one_chip, (rows, hidden))

    def loss(h_, g_):
        return jnp.sum(bucketed_spmm(g_, h_, interpret=False, stream=stream))

    compiled = jax.jit(jax.value_and_grad(loss)).lower(h, g).compile()
    assert _kernel_calls(compiled) == 2 * len(BUCKETS)


@pytest.mark.parametrize("stream", [True, False], ids=["streamed", "resident"])
def test_lmc_compensate_fwd_bwd_compiles(one_chip, stream, hidden):
    """The fused gather + lerp over the full-graph store, and its VJP."""
    n = ARXIV_ROWS - 86_720            # halo rows
    m = ARXIV_NODES if stream else RESIDENT_ROWS
    vec = _shape(one_chip, (n,))
    args = (_shape(one_chip, (m, hidden)), vec, _shape(one_chip, (n, hidden)),
            vec, _shape(one_chip, (n,), jnp.int32))

    def loss(store, beta, fresh, mask, gids):
        return jnp.sum(lmc_compensate(store, gids, beta, fresh, mask,
                                      interpret=False, stream=stream))

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
