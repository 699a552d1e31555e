"""GNNs in the paper's aggregate/update message-passing form (Eq. 2).

Every architecture is expressed through three pure functions so that the LMC
machinery (core/) can drive forward compensation and the *explicit*
message-passing backward pass (Eq. 11-13) with per-layer ``jax.vjp``:

  embed_apply(params.embed, x)                  -> H^0            (no aggregation)
  layer_apply(params.layers[l], h_in, aux)      -> h_out          (one MP layer)
  head_apply(params.head, h)                    -> logits         (output layer w)

``aux`` carries the edge list (local COO: src, dst, weight), raw features and
H^0 (for GCNII's initial-residual term). Aggregation is a weighted
segment-sum — the jnp oracle of the Pallas SpMM kernel (kernels/ref.py) —
unless ``aux.ell`` carries the rows' ``ELLGraph``: layers then aggregate
through the differentiable ``kernels.bucketed_spmm`` (its custom VJP runs
the transposed-adjacency SpMM, so the LMC per-layer ``jax.vjp`` calls stay
on the kernel; DESIGN.md §3). ``make_train_step(..., backend="ell")`` sets
it, and ``backend="ti"`` reuses the identical ELL aggregation path — the
backends differ only in how core/lmc.py compensates halo rows afterwards
(store gather vs. message-invariant rescale), which this module never sees.

Supported: GCN (Kipf & Welling 2017), GCNII (Chen et al. 2020), GraphSAGE
(Hamilton et al. 2017), GIN (Xu et al. 2019) — the families used by the paper
and its baselines.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing


class EdgeList(NamedTuple):
    src: jax.Array   # (E,) int32 local source rows
    dst: jax.Array   # (E,) int32 local destination rows
    w: jax.Array     # (E,) float32 normalized weights (0 = padding)


class LayerAux(NamedTuple):
    edges: EdgeList
    x: jax.Array          # (N, dx) raw features of the local rows
    h0: jax.Array         # (N, d) initial embedding (GCNII); zeros otherwise
    self_w: jax.Array     # (N,) self-loop weight 1/(deg+1) for GCN-normalized agg
    ell: Optional[Any] = None  # kernels.ELLGraph: aggregate via bucketed_spmm


def segment_spmm(edges: EdgeList, h: jax.Array, num_rows: int) -> jax.Array:
    """out[i] = Σ_{(j->i)} w_ji * h[j] — the reference aggregation."""
    msgs = h[edges.src] * edges.w[:, None]
    return jax.ops.segment_sum(msgs, edges.dst, num_segments=num_rows)


@dataclasses.dataclass(frozen=True)
class GNN:
    """A GNN family bound to its hyperparameters; produces pure fns + params."""

    arch: str                  # gcn | gcnii | sage | gin
    feature_dim: int
    hidden_dim: int
    num_classes: int
    num_layers: int
    alpha: float = 0.1         # GCNII initial-residual strength
    lam: float = 0.5           # GCNII identity-map strength (beta_l = log(lam/l+1))

    # ------------------------------------------------------------------ params
    def init_params(self, rng: jax.Array) -> dict:
        dx, d, c, L = self.feature_dim, self.hidden_dim, self.num_classes, self.num_layers
        ks = jax.random.split(rng, L + 2)

        def glorot(key, shape):
            lim = float(np.sqrt(6.0 / (shape[-2] + shape[-1])))
            return jax.random.uniform(key, shape, jnp.float32, -lim, lim)

        if self.arch == "gcn":
            dims = [dx] + [d] * L
            layers = {
                "w": [glorot(ks[l], (dims[l], dims[l + 1])) for l in range(L)],
                "b": [jnp.zeros((dims[l + 1],)) for l in range(L)],
            }
            embed = {}
        elif self.arch == "gcnii":
            layers = {"w": [glorot(ks[l], (d, d)) for l in range(L)]}
            embed = {"w": glorot(ks[L], (dx, d)), "b": jnp.zeros((d,))}
        elif self.arch == "sage":
            dims = [dx] + [d] * L
            layers = {
                "w_self": [glorot(ks[l], (dims[l], dims[l + 1])) for l in range(L)],
                "w_nbr": [glorot(jax.random.fold_in(ks[l], 1), (dims[l], dims[l + 1]))
                          for l in range(L)],
                "b": [jnp.zeros((dims[l + 1],)) for l in range(L)],
            }
            embed = {}
        elif self.arch == "gin":
            dims = [dx] + [d] * L
            layers = {
                "w1": [glorot(ks[l], (dims[l], dims[l + 1])) for l in range(L)],
                "b1": [jnp.zeros((dims[l + 1],)) for l in range(L)],
                "w2": [glorot(jax.random.fold_in(ks[l], 1), (dims[l + 1], dims[l + 1]))
                       for l in range(L)],
                "b2": [jnp.zeros((dims[l + 1],)) for l in range(L)],
                "eps": [jnp.zeros(()) for _ in range(L)],
            }
            embed = {}
        else:
            raise ValueError(self.arch)

        # stack per-layer params only when shapes agree (gcnii); else keep lists
        head = {"w": glorot(ks[L + 1], (d, c)), "b": jnp.zeros((c,))}
        return {"embed": embed, "layers": layers, "head": head}

    def layer_params(self, params: dict, l: int):
        return jax.tree.map(lambda leaf: leaf[l], params["layers"],
                            is_leaf=lambda leaf: isinstance(leaf, list))

    # ------------------------------------------------------------------- fns
    def embed_apply(self, embed: dict, x: jax.Array) -> jax.Array:
        if self.arch == "gcnii":
            return jax.nn.relu(x @ embed["w"] + embed["b"])
        return x  # H^0 = X for gcn/sage/gin

    def _aggregate(self, aux: LayerAux, h: jax.Array, n: int) -> jax.Array:
        """Route aggregation: Pallas ELL kernel when the batch carries an
        ELLGraph (train-step ``backend="ell"``), else the segment sum over
        the COO edges. Runs under the ``lmc.agg`` scope."""
        with jax.named_scope(tracing.AGG):
            if aux.ell is not None:
                from repro.kernels import bucketed_spmm
                return bucketed_spmm(aux.ell, h)
            return segment_spmm(aux.edges, h, n)

    def layer_apply(self, lp: dict, l: int, h_in: jax.Array, aux: LayerAux) -> jax.Array:
        """One message-passing layer over the local row set (batch + halo):
        the aggregation under the ``lmc.agg`` scope, the rest under
        ``lmc.dense``."""
        n = h_in.shape[0]
        agg = self._aggregate(aux, h_in, n)
        if self.arch == "sage":
            with jax.named_scope(tracing.AGG):
                deg = jax.ops.segment_sum(aux.edges.w, aux.edges.dst,
                                          num_segments=n)
        with jax.named_scope(tracing.DENSE):
            if self.arch == "gcn":
                agg = agg + aux.self_w[:, None] * h_in
                return jax.nn.relu(agg @ lp["w"] + lp["b"])
            if self.arch == "gcnii":
                agg = agg + aux.self_w[:, None] * h_in
                beta_l = float(np.log(self.lam / (l + 1) + 1.0))
                sup = (1 - self.alpha) * agg + self.alpha * aux.h0
                out = (1 - beta_l) * sup + beta_l * (sup @ lp["w"])
                return jax.nn.relu(out)
            if self.arch == "sage":
                agg = agg / jnp.maximum(deg, 1e-9)[:, None]
                return jax.nn.relu(h_in @ lp["w_self"] + agg @ lp["w_nbr"]
                                   + lp["b"])
            if self.arch == "gin":
                agg = agg + (1.0 + lp["eps"]) * h_in
                hid = jax.nn.relu(agg @ lp["w1"] + lp["b1"])
                return jax.nn.relu(hid @ lp["w2"] + lp["b2"])
        raise ValueError(self.arch)

    def head_apply(self, head: dict, h: jax.Array) -> jax.Array:
        return h @ head["w"] + head["b"]

    def layer_out_dim(self, l: int) -> int:
        return self.hidden_dim

    # ----------------------------------------------------- full-graph forward
    def full_forward(self, params: dict, x: jax.Array, edges: EdgeList,
                     self_w: jax.Array) -> jax.Array:
        """Exact full-batch forward -> logits (evaluation / full-batch GD)."""
        h0 = self.embed_apply(params["embed"], x)
        aux = LayerAux(edges=edges, x=x, h0=h0, self_w=self_w)
        h = h0
        for l in range(self.num_layers):
            h = self.layer_apply(self.layer_params(params, l), l, h, aux)
        return self.head_apply(params["head"], h)


def make_gnn(arch: str, feature_dim: int, hidden_dim: int, num_classes: int,
             num_layers: int, **kw: Any) -> GNN:
    return GNN(arch=arch, feature_dim=feature_dim, hidden_dim=hidden_dim,
               num_classes=num_classes, num_layers=num_layers, **kw)


def full_edge_list(indptr: np.ndarray, indices: np.ndarray,
                   weights: np.ndarray) -> EdgeList:
    src = np.repeat(np.arange(indptr.shape[0] - 1), np.diff(indptr)).astype(np.int32)
    return EdgeList(src=jnp.asarray(indices.astype(np.int32)),
                    dst=jnp.asarray(src),
                    w=jnp.asarray(weights))
