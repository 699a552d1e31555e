"""GCNII (Chen et al., 2020) for the plain reference, float32.

``H^0 = relu(X W_e + b_e)``; layer ``l`` (counted from 0) is
``relu(((1-alpha) A_hat h + alpha H^0) ((1-beta_l) I + beta_l W_l))`` with
``beta_l = log(lambda / (l+1) + 1)``; a linear head maps the last layer to
the classes. Weights are laid out as the program's GCNII keeps them:
``{"embed": {"w", "b"}, "layers": {"w": [...]}, "head": {"w", "b"}}``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

EMBED_HAS_PARAMS = True
LAYER0_INPUT_IS_H0 = True    # layer 0 reads H^0, whose gradient reaches W_e


def _glorot(key, shape):
    lim = (6.0 / (shape[0] + shape[1])) ** 0.5
    return jax.random.uniform(key, shape, jnp.float32, -lim, lim)


def init_params(key, cfg: dict) -> dict:
    dx, d, c, L = (cfg["graph"]["features"], cfg["hidden_dim"],
                   cfg["graph"]["classes"], cfg["num_layers"])
    ks = jax.random.split(key, L + 2)
    return {
        "embed": {"w": _glorot(ks[L], (dx, d)),
                  "b": jnp.zeros((d,), jnp.float32)},
        "layers": {"w": [_glorot(ks[l], (d, d)) for l in range(L)]},
        "head": {"w": _glorot(ks[L + 1], (d, c)),
                 "b": jnp.zeros((c,), jnp.float32)},
    }


def layer_params(params: dict, l: int) -> dict:
    return {"w": params["layers"]["w"][l]}


def embed(params: dict, x, mm):
    return jax.nn.relu(mm(x, params["embed"]["w"]) + params["embed"]["b"])


def layer(lp: dict, l: int, h, h0, agg, mm, cfg: dict):
    alpha, lam = cfg["arch_args"]["alpha"], cfg["arch_args"]["lam"]
    beta_l = math.log(lam / (l + 1) + 1.0)
    sup = (1.0 - alpha) * agg(h) + alpha * h0
    return jax.nn.relu((1.0 - beta_l) * sup + beta_l * mm(sup, lp["w"]))


def head(params: dict, h, mm):
    return mm(h, params["head"]["w"]) + params["head"]["b"]
