"""Operations and bytes that an LMC step requires, from its real sizes.

Counts use the batch's real rows and edges, never the padded capacity, so
a change that skips padding work raises the shares computed from them and
leaves the counts alone. A floating-point operation is a multiply or an
add; a multiply-add is two.

``step_flops`` counts what ``mfu`` divides: the dense transforms and the
aggregations of the forward pass, and of both differentiations of each layer
that LMC needs (the parameter gradient from the batch adjoint, the input
adjoint from batch plus halo adjoint). Elementwise work (activations,
compensation, softmax) is left out. ``spmm`` and ``compensate`` count one
kernel call for its roofline.
"""
from __future__ import annotations

import dataclasses

import numpy as np

F32 = 4  # bytes of a float32 or an int32


@dataclasses.dataclass(frozen=True)
class BatchSizes:
    """Real sizes of one mini-batch."""
    batch_rows: int     # |V_B|
    halo_rows: int      # 1-hop nodes outside V_B
    edges: int          # directed edges carrying a message (into batch
                        # rows, and into halo rows from inside the subgraph)
    edge_src_rows: int  # distinct rows that send a message
    edge_dst_rows: int  # distinct rows that receive one

    @property
    def rows(self) -> int:
        return self.batch_rows + self.halo_rows


def batch_sizes(edge_src: np.ndarray, edge_dst: np.ndarray, batch_rows: int,
                halo_rows: int, edges: int) -> BatchSizes:
    """Sizes of a batch from its real edge list (padding already cut)."""
    src, dst = edge_src[:edges], edge_dst[:edges]
    return BatchSizes(batch_rows=int(batch_rows), halo_rows=int(halo_rows),
                      edges=int(edges), edge_src_rows=int(np.unique(src).size),
                      edge_dst_rows=int(np.unique(dst).size))


def layer_widths(cfg: dict) -> list[tuple[int, int]]:
    """(input, output) width of each message-passing layer."""
    dx, d, L = cfg["graph"]["features"], cfg["hidden_dim"], cfg["num_layers"]
    if cfg["arch"] == "gcn":
        return [(dx if l == 0 else d, d) for l in range(L)]
    if cfg["arch"] == "gcnii":
        return [(d, d)] * L
    raise ValueError(f"no counts for arch {cfg['arch']!r}")


def needs_input_adjoint(cfg: dict, l: int) -> bool:
    """Whether layer ``l``'s input adjoint is needed: above layer 0 always;
    at layer 0 only where the input has parameters below it (GCNII's H^0)."""
    return l > 0 or cfg["arch"] == "gcnii"


def step_flops(cfg: dict, b: BatchSizes) -> float:
    """Floating-point operations one LMC step requires."""
    n, nb, e = b.rows, b.batch_rows, b.edges
    c, d = cfg["graph"]["classes"], cfg["hidden_dim"]
    macs = 0
    for l, (din, dout) in enumerate(layer_widths(cfg)):
        agg = e * din + n * din            # neighbour messages + self term
        macs += agg + n * din * dout       # forward
        macs += nb * din * dout            # weight gradient, batch adjoint
        if needs_input_adjoint(cfg, l):
            macs += n * dout * din + agg   # input adjoint, transposed agg.
    macs += n * d * c + nb * d * c + n * d * c   # head: logits, dW, dh
    if cfg["arch"] == "gcnii":
        dx = cfg["graph"]["features"]
        macs += n * dx * d + nb * dx * d   # embedding and its gradient
    return 2.0 * macs


def spmm_calls(cfg: dict, b: BatchSizes) -> list[tuple[float, float]]:
    """(flops, bytes) of every aggregation kernel call one step requires:
    one per layer forward, one transposed per needed input adjoint."""
    calls = []
    widths = layer_widths(cfg)
    for din, _ in widths:
        calls.append(spmm(b.edges, b.edge_src_rows, b.edge_dst_rows, din))
    for l, (din, _) in enumerate(widths):
        if needs_input_adjoint(cfg, l):
            calls.append(spmm(b.edges, b.edge_dst_rows, b.edge_src_rows, din))
    return calls


def spmm(pairs: int, src_rows: int, dst_rows: int, width: int):
    """One ``out[dst] += w * h[src]`` over ``pairs`` real (row, neighbour)
    pairs: each source row read once, each pair's index and weight read,
    each destination row written once. This is the least traffic any
    kernel can have, so the share it gives cannot pass 100%."""
    flops = 2.0 * pairs * width
    nbytes = F32 * (width * (src_rows + dst_rows) + 2 * pairs)
    return flops, float(nbytes)


def compensate_calls(cfg: dict, b: BatchSizes) -> list[tuple[float, float]]:
    """(flops, bytes) of every compensation kernel call one step requires:
    one per layer forward (H-bar), one per layer above 0 backward (V-bar)."""
    L, d = cfg["num_layers"], cfg["hidden_dim"]
    return [compensate(b.halo_rows, d)] * (2 * L - 1)


def compensate(rows: int, width: int):
    """``mask * ((1 - beta) * store[gid] + beta * fresh)`` over ``rows``
    halo rows: gather a store row, read the fresh row, write the blend, and
    read each row's id, beta and mask."""
    flops = 4.0 * rows * width
    nbytes = F32 * (3 * rows * width + 3 * rows)
    return flops, float(nbytes)


def roofline_seconds(calls, peak_flops: float, peak_bw: float) -> float:
    """Least time for the calls: each takes the larger of operations over
    peak rate and bytes over peak bandwidth. Both kernels move about eight
    bytes per operation, so bandwidth is the bound for every call."""
    return sum(max(flops / peak_flops, nbytes / peak_bw)
               for flops, nbytes in calls)
