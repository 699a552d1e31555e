"""The LMC step's device scopes, read from the compiled HLO's op_name paths.

The step of a tiny GCNII is compiled on the ``segment`` and ``ell``
(interpreted Pallas) backends, and a GCN at the published width 256 on
``ell``. Every op carries at most one of the four
scopes of ``repro.tracing``, all four are present, transposed aggregation is
told by its path, every scatter and dot carries a scope, and the number of
aggregations the compiled step holds is pinned: one forward and one
transposed a layer (the vjp's unused batch-only adjoint is dead code).
"""
import re

import jax
import pytest

from repro import tracing
from repro.core import LMC, from_graph, init_history, make_train_step, \
    to_device_batch
from repro.graph import ClusterSampler
from repro.models import make_gnn

LAYERS = 3
ELL_BUCKETS = 3   # host_batch's default (8, 32, 128)
TRANSPOSED_AGG = "transpose(jvp(" + tracing.AGG
INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = .*?\s([a-z][\w-]*)\(")
OP_NAME = re.compile(r'op_name="([^"]*)"')


def _instructions(hlo: str):
    """(computation, opcode, op_name or None) of every HLO instruction."""
    comp = None
    for line in hlo.splitlines():
        if line.startswith(("ENTRY", "%")) and line.rstrip().endswith("{"):
            comp = "ENTRY" if line.startswith("ENTRY") else line.split()[0]
            continue
        m = INSTR.match(line)
        if m:
            on = OP_NAME.search(line)
            yield comp, m.group(2), on.group(1) if on else None


def _scopes(op_name: str) -> set:
    return {s for s in tracing.SCOPES if s in op_name}


def _spmm_calls(op_names) -> int:
    """Interpreted ell_spmm calls among top-level ``while`` op names. A call
    is its grid loop; where the grid has one step (a bucket of one row tile
    and one lane block), XLA inlines that loop and leaves the step's one
    loop at the top, the K loop (the first plane's row copies sit in a
    conditional on the tile's plane count)."""
    grid = sum(on.endswith("jit(ell_spmm)/while") for on in op_names)
    inlined = sum(on.endswith("jit(ell_spmm)/while/body/while")
                  for on in op_names)
    return grid + inlined


@pytest.fixture(scope="module", params=["segment", "ell"])
def compiled(request, small_graph, small_parts):
    g = small_graph
    sampler = ClusterSampler(g, 16, 2, parts=small_parts, seed=1)
    gnn = make_gnn("gcnii", g.feature_dim, 16, g.num_classes, LAYERS)
    data = from_graph(g)
    batch = to_device_batch(sampler.sample(), backend=request.param)
    step = jax.jit(make_train_step(gnn, LMC, g.num_nodes,
                                   backend=request.param))
    lowered = step.lower(gnn.init_params(jax.random.key(0)),
                         init_history(LAYERS, g.num_nodes, 16), batch,
                         data.x, data.self_w)
    return request.param, list(_instructions(lowered.compile().as_text()))


def test_scopes_are_present_and_disjoint(compiled):
    _, instrs = compiled
    names = [on for _, _, on in instrs if on]
    for scope in tracing.SCOPES:
        assert any(scope in on for on in names), scope
    both = [on for on in names if len(_scopes(on)) > 1]
    assert not both, both[:5]


def test_transposed_aggregation_is_marked(compiled):
    backend, instrs = compiled
    names = [on for _, _, on in instrs if on]
    assert any(TRANSPOSED_AGG in on for on in names)
    if backend == "ell":
        # the custom VJP's transposed-adjacency SpMM
        assert any(TRANSPOSED_AGG in on and "ell_spmm" in on for on in names)


def test_every_scatter_and_dot_carries_a_scope(compiled):
    _, instrs = compiled
    ops = [(op, on) for _, op, on in instrs if op in ("scatter", "dot")]
    assert any(op == "scatter" for op, _ in ops)
    assert any(op == "dot" for op, _ in ops)
    bare = [(op, on) for op, on in ops if not on or not _scopes(on)]
    assert not bare, bare[:5]


def test_aggregations_per_layer(compiled):
    """One forward and one transposed aggregation a layer survive
    compilation: the re-linearised forward is merged with the first, and
    the batch-only adjoint's transposed aggregation is dropped."""
    backend, instrs = compiled
    top = [(op, on) for comp, op, on in instrs if comp == "ENTRY" and on]
    if backend == "segment":
        # an aggregation is one scatter-add over the edges (in a fusion)
        aggs = [on for _, on in top
                if tracing.AGG in on and on.endswith("/scatter-add")]
        calls, per_call = len, 1
    else:
        # an aggregation is one interpreted ell_spmm call per bucket
        aggs = [on for op, on in top
                if op == "while" and tracing.AGG in on and "ell_spmm" in on]
        calls, per_call = _spmm_calls, ELL_BUCKETS
    transposed = [on for on in aggs if TRANSPOSED_AGG in on]
    forward = [on for on in aggs if TRANSPOSED_AGG not in on]
    assert calls(forward) == LAYERS * per_call
    assert calls(transposed) == LAYERS * per_call


@pytest.fixture(scope="module")
def compiled_gcn_256(small_graph, small_parts):
    """The ``ell`` step of a GCN at 256 lanes (two lane blocks a kernel)."""
    g = small_graph
    sampler = ClusterSampler(g, 16, 2, parts=small_parts, seed=1)
    gnn = make_gnn("gcn", g.feature_dim, 256, g.num_classes, LAYERS)
    step = jax.jit(make_train_step(gnn, LMC, g.num_nodes, backend="ell"))
    data = from_graph(g)
    lowered = step.lower(gnn.init_params(jax.random.key(0)),
                         init_history(LAYERS, g.num_nodes, 256),
                         to_device_batch(sampler.sample(), backend="ell"),
                         data.x, data.self_w)
    return [on for comp, op, on in _instructions(lowered.compile().as_text())
            if comp == "ENTRY" and op == "while" and on]


def test_kernels_carry_their_scopes_at_hidden_256(compiled_gcn_256):
    """Each interpreted kernel loop carries the scope its readers read: the
    forward SpMM ``lmc.agg``, the custom VJP's transposed SpMM
    ``transpose(jvp(lmc.agg``, the compensation kernel ``lmc.halo``. A GCN
    needs no input adjoint at layer 0: one transposed SpMM a layer above."""
    spmm = [on for on in compiled_gcn_256 if "ell_spmm" in on]
    transposed = [on for on in spmm if "transpose(" in on]
    forward = [on for on in spmm if "transpose(" not in on]
    assert _spmm_calls(forward) == LAYERS * ELL_BUCKETS
    assert all(tracing.AGG in on and TRANSPOSED_AGG not in on
               for on in forward)
    assert _spmm_calls(transposed) == (LAYERS - 1) * ELL_BUCKETS
    assert all(TRANSPOSED_AGG in on for on in transposed)
    compensate = [on for on in compiled_gcn_256 if "lmc_compensate" in on]
    assert compensate and all(tracing.HALO in on for on in compensate)
