"""Local Message Compensation — the paper's Algorithm 1, in JAX.

One unified, jit-compiled train step implements LMC, GAS, Cluster-GCN and the
C_f/C_b ablations (see core/methods.py). The backward pass is *explicit*
message passing (paper Eq. 11–13) built from per-layer ``jax.vjp`` calls — not
autodiff through the stale forward:

  * cotangent ``[V̄_batch ; V̂_halo]``  -> adjoint recursion (Eqs. 11 & 13)
  * cotangent ``[V̄_batch ; 0]``       -> θ-gradients (Eq. 7 sums in-batch rows only)

Both are evaluations of the same linear vjp, so LMC costs exactly one extra
cotangent application per layer versus GAS — matching the paper's complexity
table (Table 5).
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.core.history import HistoricalState, gather_rows, scatter_rows
from repro.core.methods import MBMethod
from repro.dist.sharding import concat_rows
from repro.graph.structure import PaddedSubgraph
from repro.kernels import ELLGraph, ell_from_coo, lmc_compensate
from repro.models.gnn import GNN, EdgeList, LayerAux

AGG_BACKENDS = ("segment", "ell", "ti")


class Batch(NamedTuple):
    """Device-side view of a PaddedSubgraph (all jnp arrays).

    ``ell`` (optional) carries the batch-local adjacency re-bucketed into the
    Pallas kernel's padded-ELL layout (built host-side by ``to_device_batch``
    with fixed per-bucket capacities, so every batch of a sampler shares one
    jit trace); required by ``make_train_step(..., backend="ell"|"ti")``.

    ``ti_scale`` (optional) carries the per-halo-row message-invariance
    scales α (graph/structure.py builds them next to β); required by
    ``backend="ti"``, whose compensation is α ⊙ fresh instead of a
    historical-store gather (DESIGN.md §11).
    """
    batch_gids: jax.Array
    halo_gids: jax.Array
    batch_mask: jax.Array
    halo_mask: jax.Array
    edge_src: jax.Array
    edge_dst: jax.Array
    edge_w: jax.Array
    labels: jax.Array
    labeled_mask: jax.Array
    beta: jax.Array
    loss_scale: jax.Array
    grad_scale: jax.Array
    ell: Optional[ELLGraph] = None
    ti_scale: Optional[jax.Array] = None


def host_batch(sg: PaddedSubgraph, *, backend: str = "segment",
               ell_buckets=(8, 32, 128),
               record: Optional[dict] = None) -> Batch:
    """Build a Batch of *host* (numpy) arrays, including the re-bucketed ELL
    adjacency for ``backend="ell"|"ti"`` — everything except the device
    transfer.

    This is the per-batch work the async pipeline runs on worker threads
    (pure numpy, no JAX calls, so workers never contend on device dispatch);
    the consumer moves the whole pytree over with one ``jax.device_put``
    (DESIGN.md §9). ``to_device_batch`` composes the two for the synchronous
    path. ``backend="ti"`` additionally rides the subgraph's α scales along
    (the halo-compensation transform — no store state needed at step time).
    The ELL build runs under the host span ``pipeline.ell``, whose seconds
    go to ``record["ell_s"]`` when a record is given.
    """
    assert backend in AGG_BACKENDS, backend
    ell = None
    ti_scale = None
    if backend in ("ell", "ti"):
        with tracing.span("pipeline.ell", record):
            ell = ell_from_coo(sg.edge_src, sg.edge_dst, sg.edge_w, sg.n_ext,
                               buckets=ell_buckets, as_jax=False)
    if backend == "ti":
        if sg.ti_scale is None:
            raise ValueError(
                'backend="ti" needs PaddedSubgraph.ti_scale; rebuild the '
                "subgraph with graph.structure.build_subgraph (any sampler "
                "batch has it)")
        ti_scale = np.asarray(sg.ti_scale)
    return Batch(
        batch_gids=np.asarray(sg.batch_gids), halo_gids=np.asarray(sg.halo_gids),
        batch_mask=np.asarray(sg.batch_mask), halo_mask=np.asarray(sg.halo_mask),
        edge_src=np.asarray(sg.edge_src), edge_dst=np.asarray(sg.edge_dst),
        edge_w=np.asarray(sg.edge_w), labels=np.asarray(sg.labels),
        labeled_mask=np.asarray(sg.labeled_mask), beta=np.asarray(sg.beta),
        loss_scale=np.asarray(sg.loss_scale), grad_scale=np.asarray(sg.grad_scale),
        ell=ell, ti_scale=ti_scale)


def batch_fills(sg: PaddedSubgraph, batch: Batch) -> dict:
    """How full a batch's padded layouts are: ``edge_fill``, the real edges
    over the padded edge count, and ``ell_fill``, the real (row, neighbour)
    pairs over the slots of its forward ELL (0.0 without an ELL)."""
    slots = (0 if batch.ell is None else
             sum(idx.size for idx in batch.ell.bucket_idx))
    return {"edge_fill": sg.edge_fill,
            "ell_fill": sg.n_edges_real / slots if slots else 0.0}


def to_device_batch(sg: PaddedSubgraph, *, backend: str = "segment",
                    ell_buckets=(8, 32, 128)) -> Batch:
    """Host subgraph -> device Batch (``host_batch`` + ``jax.device_put``)."""
    return jax.device_put(host_batch(sg, backend=backend,
                                     ell_buckets=ell_buckets))


def _combine(mode: str, beta: jax.Array, hist: jax.Array, fresh: jax.Array,
             mask: jax.Array) -> jax.Array:
    """Convex combination of historical and incomplete-fresh values (Eq. 9/12)."""
    if mode == "lmc":
        out = (1.0 - beta) * hist + beta * fresh
    elif mode == "historical":
        out = hist
    elif mode == "fresh":
        out = fresh
    elif mode == "none":
        out = jnp.zeros_like(fresh)
    else:
        raise ValueError(mode)
    return out * mask


def _compensate(mode: str, backend: str, store: Optional[jax.Array], l: int,
                halo_gids: jax.Array, beta1d: jax.Array, fresh: jax.Array,
                mask1d: jax.Array, stream: Optional[bool] = None,
                ti_scale: Optional[jax.Array] = None) -> jax.Array:
    """Halo compensation ĥ/V̂ (Eq. 9/12): gather the historical rows of
    layer ``l`` of ``store`` and convex-combine with the incomplete fresh
    values, under the ``lmc.halo`` scope. ``store`` may be None where no
    mode reads it (``backend="ti"``).

    backend="segment": jnp gather + lerp. backend="ell": one fused Pallas
    ``lmc_compensate`` call — every mode is the same kernel with an effective
    β (lmc: β, historical: 0, fresh: 1); "none" skips the gather entirely.
    ``stream`` (default: autodetect) selects the HBM→VMEM DMA store gather —
    the store is *full-graph* here, so the streamed path is what lets the
    compiled kernel run at paper scale (DESIGN.md §3).

    backend="ti": the message-invariance estimator (DESIGN.md §11) — the
    historical row H̄_i is replaced by the message-invariant transform
    α_i ⊙ h̃_i of the *in-batch* fresh value, so Eq. 9/12 collapse to an
    elementwise rescale ``((1-β_eff)·α + β_eff) ⊙ fresh`` with the same
    effective-β trick. No store read, no gather, no kernel: strictly less
    memory traffic than either store-reading backend.
    """
    with jax.named_scope(tracing.HALO):
        if mode == "none":
            return jnp.zeros_like(fresh)
        if backend == "ti":
            beta_eff = {"lmc": beta1d,
                        "historical": jnp.zeros_like(beta1d),
                        "fresh": jnp.ones_like(beta1d)}[mode]
            coeff = (1.0 - beta_eff) * ti_scale + beta_eff
            return fresh * (coeff * mask1d)[:, None]
        if backend == "ell":
            beta_eff = {"lmc": beta1d,
                        "historical": jnp.zeros_like(beta1d),
                        "fresh": jnp.ones_like(beta1d)}[mode]
            return lmc_compensate(store[l], halo_gids, beta_eff, fresh,
                                  mask1d, stream=stream)
        hist = gather_rows(store[l], halo_gids)
        return _combine(mode, beta1d[:, None], hist, fresh, mask1d[:, None])


def _refresh(store: jax.Array, l: int, gids: jax.Array, mask: jax.Array,
             rows: jax.Array, num_nodes: int) -> jax.Array:
    """Store refresh: write the batch rows into layer ``l`` of ``store``,
    under the ``lmc.store`` scope."""
    with jax.named_scope(tracing.STORE):
        return store.at[l].set(scatter_rows(store[l], gids, mask, rows,
                                            num_nodes))


def make_infer_step(gnn: GNN, num_nodes: int, *, backend: str = "segment",
                    fwd_mode: str = "historical", compensation: str = "store",
                    refresh: bool = True,
                    stream: Optional[bool] = None) -> Callable:
    """Build ``infer(params, store, batch, x_full, self_w_full)`` — the
    forward-only serving entry point over the historical store.

    Returns ``(logits, new_store)`` where ``logits`` covers the batch's
    padded target rows (mask with ``batch.batch_mask``). Pure; jit at call
    site, one trace per padded batch shape.

    The forward loop is the train step's (Eqs. 8-10) with the backward pass
    cut away: batch rows aggregate their *complete* neighborhood (every
    neighbor is in the padded extension), halo rows are approximated by
    ``_compensate``. Two axes:

    ``compensation="store"`` (the healthy serving path) gathers halo rows
    from ``store.h`` — with ``fwd_mode="historical"`` and a store holding
    exact layer values (core/exact.py ``exact_layer_values``), the target
    logits equal the full-graph forward exactly, at mini-batch cost: the
    store IS the receptive field. ``compensation="ti"`` substitutes the
    message-invariance transform α ⊙ fresh for every store read (DESIGN.md
    §11) — the store-free degraded mode with Fig.-3-bounded bias, also the
    repair path (recompute rows without trusting the store).

    ``refresh=True`` scatters the freshly computed batch rows back into the
    store (the read path through ``lmc_compensate`` under ``backend="ell"``);
    on the exact path this keeps refreshed rows exact, and under
    ``compensation="ti"`` it *heals* poisoned/stale rows from store-free
    values. ``refresh=False`` is the strictly read-only mode — with
    ``compensation="ti"`` the store is provably dead in the jaxpr.

    ``backend`` selects aggregation only ("segment" | "ell" Pallas SpMM);
    degradation swaps the compensation, never the aggregation kernel, so
    both modes share the compiled trace shape.
    """
    assert backend in ("segment", "ell"), backend
    assert compensation in ("store", "ti"), compensation
    assert fwd_mode in ("lmc", "historical", "fresh"), fwd_mode
    L = gnn.num_layers

    def infer(params: dict, store: HistoricalState, batch: Batch,
              x_full: jax.Array, self_w_full: jax.Array):
        nb = batch.batch_gids.shape[0]
        if backend == "ell" and batch.ell is None:
            raise ValueError(
                'backend="ell" needs batch.ell; build the batch with '
                'to_device_batch(sg, backend="ell")')
        if compensation == "ti" and batch.ti_scale is None:
            raise ValueError(
                'compensation="ti" needs batch.ti_scale; attach the '
                "subgraph's α scales (host_batch(sg, backend=\"ti\") or "
                "Batch._replace)")
        with jax.named_scope(tracing.DENSE):
            ext_gids = concat_rows([batch.batch_gids, batch.halo_gids])
            x_ext = jnp.take(x_full, ext_gids, axis=0, mode="clip")
            self_w_ext = jnp.take(self_w_full, ext_gids, axis=0, mode="clip")
            h0_ext = gnn.embed_apply(params["embed"], x_ext)
            bmask = batch.batch_mask[:, None]
        edges = EdgeList(batch.edge_src, batch.edge_dst, batch.edge_w)
        aux = LayerAux(edges=edges, x=x_ext, h0=h0_ext, self_w=self_w_ext,
                       ell=batch.ell if backend == "ell" else None,
                       stream=stream)
        comp_backend = "ti" if compensation == "ti" else backend

        h_in = h0_ext
        new_h = store.h
        for l in range(L):
            h_out = gnn.layer_apply(gnn.layer_params(params, l), l, h_in, aux)
            with jax.named_scope(tracing.DENSE):
                h_bar_batch = h_out[:nb] * bmask
            h_hat_halo = _compensate(
                fwd_mode, comp_backend,
                None if compensation == "ti" else new_h, l,
                batch.halo_gids, batch.beta, h_out[nb:], batch.halo_mask,
                stream, batch.ti_scale)
            if refresh:
                new_h = _refresh(new_h, l, batch.batch_gids,
                                 batch.batch_mask, h_bar_batch, num_nodes)
            with jax.named_scope(tracing.DENSE):
                h_in = concat_rows([h_bar_batch, h_hat_halo], axis=0)

        with jax.named_scope(tracing.DENSE):
            logits = gnn.head_apply(params["head"], h_in[:nb])
        return logits, HistoricalState(h=new_h, v=store.v)

    return infer


def make_train_step(gnn: GNN, method: MBMethod, num_nodes: int, *,
                    backend: str = "segment",
                    stream: Optional[bool] = None) -> Callable:
    """Build ``step(params, store, batch, x_full, self_w_full)``.

    Returns ``(loss, grads, new_store, metrics)``. Pure; jit/pjit at call site
    with ``donate_argnums=(1,)`` for the store.

    ``backend`` selects the aggregation hot path: ``"segment"`` is the jnp
    segment-sum oracle; ``"ell"`` runs layer aggregation through the Pallas
    bucketed ELL SpMM (forward *and*, via its custom VJP, the per-layer
    ``jax.vjp`` cotangent applications of Eqs. 11-13) and halo compensation
    through the fused ``lmc_compensate`` kernel. The batch must then carry the
    bucketed adjacency (``to_device_batch(sg, backend="ell")``).

    ``stream`` (ell/ti backends; default autodetect = streamed) selects the
    HBM→VMEM double-buffered DMA gather in both kernels — required for
    full-graph historical stores on the compiled path; ``stream=False``
    forces the legacy resident VMEM gather blocks.

    ``backend="ti"`` aggregates through the same Pallas SpMM but compensates
    with the message-invariance estimator instead of historical rows
    (DESIGN.md §11): the step performs *zero* reads of ``store.h``/``store.v``
    and — under a ``store_writes=False`` method like ``methods.TI`` — zero
    writes, returning the input store untouched.
    """
    method.validate()
    assert backend in AGG_BACKENDS, backend
    L = gnn.num_layers
    layer0_input_is_h0 = gnn.arch == "gcnii"

    def step(params: dict, store: HistoricalState, batch: Batch,
             x_full: jax.Array, self_w_full: jax.Array):
        nb = batch.batch_gids.shape[0]
        if backend in ("ell", "ti") and batch.ell is None:
            raise ValueError(
                f'backend="{backend}" needs batch.ell; build the batch with '
                f'to_device_batch(sg, backend="{backend}")')
        if backend == "ti" and batch.ti_scale is None:
            raise ValueError(
                'backend="ti" needs batch.ti_scale; build the batch with '
                'to_device_batch(sg, backend="ti")')
        # every op runs under one of the scopes of repro.tracing; the layers'
        # vjp calls stay outside them, so a transposed op's path reads
        # transpose(jvp(<its forward scope>)) and no op carries two scopes.
        # concat_rows (not jnp.concatenate): [batch | halo] row blocks must
        # keep explicit shardings under SPMD — see repro.dist.sharding
        with jax.named_scope(tracing.DENSE):
            ext_gids = concat_rows([batch.batch_gids, batch.halo_gids])
            x_ext = jnp.take(x_full, ext_gids, axis=0, mode="clip")
            self_w_ext = jnp.take(self_w_full, ext_gids, axis=0, mode="clip")
            h0_ext = gnn.embed_apply(params["embed"], x_ext)
            bmask = batch.batch_mask[:, None]
            hmask = batch.halo_mask[:, None]
        edges = EdgeList(batch.edge_src, batch.edge_dst, batch.edge_w)
        aux = LayerAux(edges=edges, x=x_ext, h0=h0_ext, self_w=self_w_ext,
                       ell=batch.ell if backend in ("ell", "ti") else None,
                       stream=stream)

        # ---------------- forward (Eqs. 8-10) --------------------------------
        h_in = h0_ext
        residuals = []
        new_h = store.h
        for l in range(L):
            residuals.append(h_in)
            h_out = gnn.layer_apply(gnn.layer_params(params, l), l, h_in, aux)
            with jax.named_scope(tracing.DENSE):
                h_bar_batch = h_out[:nb] * bmask
            # ti never touches the store — don't even slice it (keeps the
            # store inputs provably dead in the step's jaxpr)
            h_hat_halo = _compensate(method.fwd_mode, backend,
                                     None if backend == "ti" else new_h, l,
                                     batch.halo_gids, batch.beta, h_out[nb:],
                                     batch.halo_mask, stream, batch.ti_scale)
            if method.store_writes:
                new_h = _refresh(new_h, l, batch.batch_gids, batch.batch_mask,
                                 h_bar_batch, num_nodes)
            with jax.named_scope(tracing.DENSE):
                h_in = concat_rows([h_bar_batch, h_hat_halo], axis=0)

        # ---------------- loss & top-layer adjoints (Eq. 6/14 + V^L init) ----
        with jax.named_scope(tracing.DENSE):
            inv_vl = batch.loss_scale / batch.grad_scale  # = 1/|V_L|
            mask_b = batch.labeled_mask.at[nb:].set(0.0)
            mask_h = batch.labeled_mask.at[:nb].set(0.0)

            def unit_loss(head, h_rows, m):
                logits = gnn.head_apply(head, h_rows)
                logp = jax.nn.log_softmax(logits)
                ll = jnp.take_along_axis(logp, batch.labels[:, None], axis=-1)[:, 0]
                return -jnp.sum(ll * m) * inv_vl, logits

            (f1, logits_ext), vjp1 = jax.vjp(
                lambda hd, h: unit_loss(hd, h, mask_b), params["head"], h_in, has_aux=False)
            g_head_unit, V1 = vjp1((jnp.asarray(1.0, f1.dtype), jnp.zeros_like(logits_ext)))
            V_bar = V1[:nb] * bmask

            if method.bwd_mode == "none":
                V_hat = jnp.zeros_like(V1[nb:])
            else:
                (f2, _), vjp2 = jax.vjp(
                    lambda h: unit_loss(params["head"], h, mask_h), h_in)
                (V2,) = vjp2((jnp.asarray(1.0, f1.dtype), jnp.zeros_like(logits_ext)))
                V_hat = V2[nb:] * hmask

        # ---------------- backward message passing (Eqs. 11-13, 7/15) --------
        grads_layers = [None] * L
        with jax.named_scope(tracing.DENSE):
            v0_acc = jnp.zeros_like(h0_ext)
        new_v = store.v
        for l in reversed(range(L)):
            lp = gnn.layer_params(params, l)

            def f(lp_, hin_, h0_, _l=l):
                return gnn.layer_apply(lp_, _l, hin_, aux._replace(h0=h0_))

            _, vjp_fn = jax.vjp(f, lp, residuals[l], h0_ext)
            with jax.named_scope(tracing.DENSE):
                ct_batch = concat_rows([V_bar, jnp.zeros_like(V_hat)], axis=0)
            g_lp, hgrad_b, h0grad_b = vjp_fn(ct_batch)
            grads_layers[l] = g_lp
            if method.bwd_mode == "none":
                hgrad, h0grad = hgrad_b, h0grad_b
            else:
                with jax.named_scope(tracing.DENSE):
                    ct_full = concat_rows([V_bar, V_hat], axis=0)
                _, hgrad, h0grad = vjp_fn(ct_full)
            with jax.named_scope(tracing.DENSE):
                v0_acc = v0_acc + h0grad
            if l >= 1:
                with jax.named_scope(tracing.DENSE):
                    V_bar_next = hgrad[:nb] * bmask
                V_hat = _compensate(method.bwd_mode, backend,
                                    None if backend == "ti" else new_v, l - 1,
                                    batch.halo_gids, batch.beta, hgrad[nb:],
                                    batch.halo_mask, stream, batch.ti_scale)
                if method.store_writes:
                    new_v = _refresh(new_v, l - 1, batch.batch_gids,
                                     batch.batch_mask, V_bar_next, num_nodes)
                V_bar = V_bar_next
            elif layer0_input_is_h0:
                with jax.named_scope(tracing.DENSE):
                    v0_acc = v0_acc + hgrad

        # ---------------- parameter gradients (Eq. 7 with A.3.1 scaling) -----
        with jax.named_scope(tracing.DENSE):
            scale = batch.grad_scale
            grads = {
                "layers": jax.tree.map(lambda *xs: [scale * x for x in xs],
                                       *grads_layers),
                "head": jax.tree.map(lambda x: scale * x, g_head_unit),
            }
            if params["embed"]:
                _, vjp_emb = jax.vjp(lambda e: gnn.embed_apply(e, x_ext), params["embed"])
                (g_emb,) = vjp_emb(v0_acc * concat_rows(
                    [bmask, jnp.zeros_like(hmask)], axis=0))
                grads["embed"] = jax.tree.map(lambda x: scale * x, g_emb)
            else:
                grads["embed"] = {}

            # ---------------- metrics ---------------------------------------
            loss = f1 * scale
            pred = jnp.argmax(logits_ext[:nb], axis=-1)
            lab_b = mask_b[:nb]
            acc = jnp.sum((pred == batch.labels[:nb]) * lab_b) / jnp.maximum(
                jnp.sum(lab_b), 1.0)
            metrics = {"loss": loss, "train_acc": acc}
        return loss, grads, HistoricalState(h=new_h, v=new_v), metrics

    return step
