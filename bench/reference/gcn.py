"""GCN (Kipf and Welling, 2017) for the plain reference, float32.

One layer is ``relu((A_hat h) W + b)`` with ``A_hat`` the symmetric
normalisation with self loops. ``H^0 = X``: there is no input embedding, and
a linear head maps the last layer to the classes. Weights are laid out as the
program's GCN keeps them, so the benchmark can load the same arrays into it:
``{"embed": {}, "layers": {"w": [...], "b": [...]}, "head": {"w", "b"}}``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

EMBED_HAS_PARAMS = False
LAYER0_INPUT_IS_H0 = False   # layer 0 reads X, which needs no gradient


def _glorot(key, shape):
    lim = (6.0 / (shape[0] + shape[1])) ** 0.5
    return jax.random.uniform(key, shape, jnp.float32, -lim, lim)


def init_params(key, cfg: dict) -> dict:
    dx, d, c, L = (cfg["graph"]["features"], cfg["hidden_dim"],
                   cfg["graph"]["classes"], cfg["num_layers"])
    dims = [dx] + [d] * L
    ks = jax.random.split(key, L + 1)
    return {
        "embed": {},
        "layers": {"w": [_glorot(ks[l], (dims[l], dims[l + 1]))
                         for l in range(L)],
                   "b": [jnp.zeros((dims[l + 1],), jnp.float32)
                         for l in range(L)]},
        "head": {"w": _glorot(ks[L], (d, c)),
                 "b": jnp.zeros((c,), jnp.float32)},
    }


def layer_params(params: dict, l: int) -> dict:
    return {"w": params["layers"]["w"][l], "b": params["layers"]["b"][l]}


def embed(params: dict, x, mm):
    del params, mm
    return x


def layer(lp: dict, l: int, h, h0, agg, mm, cfg: dict):
    del l, h0, cfg
    return jax.nn.relu(mm(agg(h), lp["w"]) + lp["b"])


def head(params: dict, h, mm):
    return mm(h, params["head"]["w"]) + params["head"]["b"]
