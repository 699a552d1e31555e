"""Plain reference of one LMC training step and of AdamW, in jax.numpy.

It follows the paper (Shi et al., ICLR 2023, Algorithm 1) and shares no code
with the program. Every array spans all ``n`` nodes of the graph, and the
mini-batch is a mask: ``V_B`` is the batch, the halo is every node with an
edge to ``V_B`` that is not in it, and an edge carries a message when both
of its ends are in ``V_B`` or the halo. So one compiled step serves every
batch and every seed of a configuration.

Forward (Eqs. 8-10): a layer's batch rows are exact; its halo rows are
compensated, ``(1 - beta) * Hbar[l] + beta * h``, with
``beta = 2x - x^2`` and ``x`` the share of a halo node's edges that lie
inside the subgraph. The batch rows are written to ``Hbar[l]``.

Backward (Eqs. 11-15): the top adjoint of the batch rows comes from the loss
over labelled batch rows, that of the halo rows from the loss over labelled
halo rows. Each layer is differentiated twice: with the batch adjoint alone,
for the parameter gradient, and with batch plus compensated halo adjoint,
for the adjoint of the layer below, whose halo rows are compensated with
``Vbar`` as in the forward and whose batch rows are written to ``Vbar``.
Gradients and loss are scaled by ``B / c`` (parts over clusters per batch),
and the loss is normalised by the number of labelled nodes of the graph.

``mm`` is the matrix product: ``dot_highest`` (float32 accuracy, the
configurations' stated precision) or ``dot_bf16x3`` (three bfloat16 passes,
the control). ``fault="half_batch"`` drops every second labelled batch row
from the loss and takes the mean over the rest (a planted fault).
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


def dot_highest(a, b):
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST)


def _split(a):
    # reduce_precision, not a float32 -> bfloat16 -> float32 round trip:
    # XLA may drop such a round trip (excess precision), which on the TPU
    # made ``lo`` zero and the control a single bfloat16 pass
    hi = jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
    lo = jax.lax.reduce_precision(a - hi, exponent_bits=8, mantissa_bits=7)
    return hi.astype(jnp.bfloat16), lo.astype(jnp.bfloat16)


def _dot3(a, b):
    a_hi, a_lo = _split(a)
    b_hi, b_lo = _split(b)

    def d(x, y):
        return jnp.dot(x, y, preferred_element_type=jnp.float32)
    return d(a_hi, b_hi) + d(a_hi, b_lo) + d(a_lo, b_hi)


@jax.custom_vjp
def dot_bf16x3(a, b):
    """``a @ b`` in three bfloat16 passes, as TPU precision ``high`` does;
    written out so that the CPU computes the same, forward and backward."""
    return _dot3(a, b)


def _dot3_fwd(a, b):
    return _dot3(a, b), (a, b)


def _dot3_bwd(res, ct):
    a, b = res
    return _dot3(ct, b.T), _dot3(a.T, ct)


dot_bf16x3.defvjp(_dot3_fwd, _dot3_bwd)

PRECISIONS = {"highest": dot_highest, "bf16x3": dot_bf16x3}


class GraphConsts(NamedTuple):
    """The whole graph on the device, in the reference's own layout."""
    x: jax.Array        # (n, dx)
    src: jax.Array      # (E,) message source of each directed edge
    dst: jax.Array      # (E,) message destination
    w: jax.Array        # (E,) 1/sqrt((deg_src+1)(deg_dst+1))
    self_w: jax.Array   # (n,) 1/(deg+1)
    deg: jax.Array      # (n,) float degree
    labels: jax.Array   # (n,) int32
    train: jax.Array    # (n,) float 1 on training nodes


def graph_consts(g) -> GraphConsts:
    """Device constants from a host CSR graph (``graphgen.HostGraph``)."""
    n = g.num_nodes
    deg = np.diff(g.indptr).astype(np.float64)
    dst = np.repeat(np.arange(n, dtype=np.int64), np.diff(g.indptr))
    src = g.indices.astype(np.int64)
    w = 1.0 / np.sqrt((deg[src] + 1.0) * (deg[dst] + 1.0))
    return GraphConsts(
        x=jnp.asarray(g.x, jnp.float32),
        src=jnp.asarray(src, jnp.int32), dst=jnp.asarray(dst, jnp.int32),
        w=jnp.asarray(w, jnp.float32),
        self_w=jnp.asarray(1.0 / (deg + 1.0), jnp.float32),
        deg=jnp.asarray(deg, jnp.float32),
        labels=jnp.asarray(g.y, jnp.int32),
        train=jnp.asarray(g.train_mask, jnp.float32))


def batch_mask(parts: np.ndarray, cluster_ids) -> np.ndarray:
    """``V_B`` as a 0/1 float mask: the nodes of the chosen clusters."""
    return np.isin(parts, np.asarray(cluster_ids)).astype(np.float32)


def make_step(arch, cfg: dict, num_parts: int, clusters: int,
              mm: Callable, fault: str | None = None) -> Callable:
    """``step(params, H, V, c, bmask) -> (loss, grads, H, V)``, to be jitted.

    ``H``: (L, n, d) historical embeddings; ``V``: (L-1, n, d) historical
    adjoints; ``c``: ``GraphConsts``; ``bmask``: (n,) batch mask.
    """
    L = cfg["num_layers"]
    b_over_c = float(num_parts) / float(clusters)

    def step(params, H, V, c: GraphConsts, bmask):
        n = bmask.shape[0]
        sum_to = lambda vals, idx: jax.ops.segment_sum(vals, idx, n)
        touches_batch = sum_to(bmask[c.dst], c.src) > 0
        hmask = jnp.where(touches_batch & (bmask == 0), 1.0, 0.0)
        ext = bmask + hmask
        live = ext[c.src] * ext[c.dst]
        ew = c.w * live
        x_in = sum_to(live, c.dst) / jnp.maximum(c.deg, 1.0)
        beta = jnp.clip(2.0 * x_in - x_in * x_in, 0.0, 1.0)[:, None]
        bm, hm = bmask[:, None], hmask[:, None]

        def agg(h):
            return sum_to(h[c.src] * ew[:, None], c.dst) + c.self_w[:, None] * h

        def layer_fn(l):
            return lambda lp, h, h0: arch.layer(lp, l, h, h0, agg, mm, cfg)

        # forward with compensated halo rows
        h0 = arch.embed(params, c.x, mm) * ext[:, None]
        h, resid, H_new = h0, [], []
        for l in range(L):
            resid.append(h)
            out = layer_fn(l)(arch.layer_params(params, l), h, h0)
            H_new.append(jnp.where(bm > 0, out, H[l]))
            h = bm * out + hm * ((1.0 - beta) * H[l] + beta * out)

        # loss over labelled batch rows, top adjoints of batch and halo rows
        lab_b = bmask * c.train
        scale = 1.0
        if fault == "half_batch":
            rank = jnp.cumsum(lab_b) * lab_b
            kept = lab_b * (jnp.mod(rank, 2.0) == 1.0)
            scale = jnp.sum(lab_b) / jnp.maximum(jnp.sum(kept), 1.0)
            lab_b = kept
        inv_vl = 1.0 / jnp.maximum(jnp.sum(c.train), 1.0)

        def nll(head_p, hh, m):
            logits = arch.head({"head": head_p}, hh, mm)
            logp = jax.nn.log_softmax(logits)
            ll = jnp.take_along_axis(logp, c.labels[:, None], axis=1)[:, 0]
            return -jnp.sum(ll * m) * inv_vl * scale

        f1, (g_head, v_top) = jax.value_and_grad(nll, argnums=(0, 1))(
            params["head"], h, lab_b)
        v_halo = jax.grad(nll, argnums=1)(params["head"], h, hmask * c.train)
        vbar, vhat = v_top * bm, v_halo * hm

        # backward message passing
        g_layers = [None] * L
        v0 = jnp.zeros_like(h0)
        V_new = [V[l] for l in range(L - 1)]
        for l in reversed(range(L)):
            _, vjp = jax.vjp(layer_fn(l), arch.layer_params(params, l),
                             resid[l], h0)
            g_layers[l] = vjp(vbar)[0]
            _, hg, h0g = vjp(vbar + vhat)
            v0 = v0 + h0g
            if l >= 1:
                vhat = hm * ((1.0 - beta) * V[l - 1] + beta * hg)
                vbar = hg * bm
                V_new[l - 1] = jnp.where(bm > 0, hg, V[l - 1])
            elif arch.LAYER0_INPUT_IS_H0:
                v0 = v0 + hg

        grads = {"head": jax.tree.map(lambda a: b_over_c * a, g_head),
                 "layers": jax.tree.map(
                     lambda *xs: [b_over_c * a for a in xs], *g_layers),
                 "embed": {}}
        if arch.EMBED_HAS_PARAMS:
            _, vjp_e = jax.vjp(lambda p: arch.embed(p, c.x, mm), params)
            g_e = vjp_e(v0 * bm)[0]["embed"]
            grads["embed"] = jax.tree.map(lambda a: b_over_c * a, g_e)
        return f1 * b_over_c, grads, jnp.stack(H_new), jnp.stack(V_new)

    return step


def adamw_update(grads, state, params, *, lr, b1, b2, eps, weight_decay,
                 clip_norm):
    """AdamW with decoupled weight decay and global-norm clipping.

    ``state`` is ``(count, m, v)``; returns ``(params, state, grad_norm)``.
    """
    count, m, v = state
    leaves = jax.tree.leaves(grads)
    gn = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
    g = jax.tree.map(lambda a: a * jnp.minimum(1.0, clip_norm
                                               / jnp.maximum(gn, 1e-9)), grads)
    t = count + 1.0
    m = jax.tree.map(lambda a, b: b1 * a + (1.0 - b1) * b, m, g)
    v = jax.tree.map(lambda a, b: b2 * a + (1.0 - b2) * b * b, v, g)
    bc1, bc2 = 1.0 - b1 ** t, 1.0 - b2 ** t
    params = jax.tree.map(
        lambda p, m_, v_: p - lr * ((m_ / bc1) / (jnp.sqrt(v_ / bc2) + eps)
                                    + weight_decay * p), params, m, v)
    return params, (t, m, v), gn
