"""The comparison that decides ``correct``: the program's first three
training steps against the plain reference's.

Both sides start from the same weights and take the same three batches.
What the program produced is read from its own state: the losses it
reported, the first step's gradient worked out from AdamW's first moment
after one step, the stores after one step, and the parameters after three.

Numbers compared (each has a limit in ``limits/<workload>.json``):

* ``loss_gap``: the largest relative gap of the three losses.
* ``grad_gap``: the worst leaf's gap between the program's and the
  reference's gradient norm, over the larger of that leaf's reference norm
  and the median leaf's.
* ``change_gap``: the same for the norm of each leaf's change over the
  three steps; leaves whose reference gradient is under a thousandth of the
  median leaf's are left out, since Adam moves them by rounding alone.
* ``hbar_gap``: the worst layer's largest elementwise gap of the refreshed
  historical embeddings, over that layer's largest reference magnitude.
* ``vbar_gap``: the worst layer's norm of the difference of the refreshed
  historical adjoints, over that layer's reference norm (elementwise gaps
  would read the ReLU derivative flipping on values within rounding of 0).
"""
from __future__ import annotations

import math

import numpy as np

NAMES = ("loss_gap", "grad_gap", "change_gap", "hbar_gap", "vbar_gap")
NEGLIGIBLE_GRAD = 1e-3   # of the median leaf's gradient norm


def flat(tree, prefix="") -> dict:
    """``{"layers.w.0": array, ...}`` for a nest of dicts and lists."""
    out = {}
    if isinstance(tree, dict):
        for k in sorted(tree):
            out.update(flat(tree[k], f"{prefix}{k}."))
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            out.update(flat(v, f"{prefix}{i}."))
    else:
        out[prefix[:-1]] = np.asarray(tree, np.float64)
    return out


def _worst(values) -> float:
    """The largest value; infinite where any is not finite."""
    values = list(values)
    if not all(math.isfinite(v) for v in values):
        return math.inf
    return max(values, default=0.0)


def _norms(tree) -> dict:
    return {k: float(np.linalg.norm(v)) for k, v in flat(tree).items()}


def _norm_gap(got: dict, want: dict, keys) -> float:
    med = float(np.median([want[k] for k in want]))
    return _worst(abs(got[k] - want[k]) / max(want[k], med, 1e-30)
                  for k in keys)


def numbers(prog: dict, ref: dict) -> dict:
    """Each side: ``losses`` (3), ``grads`` (first step's, a tree),
    ``params0`` and ``params3`` (trees), ``hbar`` (L, n, d) and ``vbar``
    (L-1, n, d) after the first step."""
    losses = _worst(abs(a - b) / max(abs(b), 1e-30)
                    for a, b in zip(prog["losses"], ref["losses"],
                                    strict=True))
    g_ref = _norms(ref["grads"])
    g_med = float(np.median(list(g_ref.values())))
    moved = [k for k, v in g_ref.items() if v >= NEGLIGIBLE_GRAD * g_med]

    def change(side):
        p0, p3 = flat(side["params0"]), flat(side["params3"])
        return {k: float(np.linalg.norm(p3[k] - p0[k])) for k in p0}

    hbar = _worst(float(np.max(np.abs(np.asarray(a, np.float64) - b)))
                  / max(float(np.max(np.abs(b))), 1e-30)
                  for a, b in zip(prog["hbar"], ref["hbar"], strict=True))
    vbar = _worst(float(np.linalg.norm(np.asarray(a, np.float64) - b))
                  / max(float(np.linalg.norm(b)), 1e-30)
                  for a, b in zip(prog["vbar"], ref["vbar"], strict=True))
    return {"loss_gap": losses,
            "grad_gap": _norm_gap(_norms(prog["grads"]), g_ref, g_ref),
            "change_gap": _norm_gap(change(prog), change(ref), moved),
            "hbar_gap": hbar, "vbar_gap": vbar}


def checks(values: dict, limits: dict) -> dict:
    """``{name: {"value", "limit"}}`` for every name in ``limits``."""
    return {k: {"value": values[k], "limit": float(limits[k]["limit"])}
            for k in NAMES if k in limits}


def passed(checked: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checked.values())
