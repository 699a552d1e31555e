"""The benchmark's own graph generator: a seeded stochastic block model.

The cells train on stand-ins for ogbn-arxiv and Flickr that keep each
dataset's node count, mean degree, class count and feature width, with a
planted community-label correlation so that training is meaningful. The
generator is the benchmark's, not the program's, so that a change to the
program cannot change the inputs it is measured on. It draws the same random
numbers in the same order as ``repro.graph.synthetic.make_sbm_dataset``, so
the graphs are identical to the repository's presets of the same sizes
(``test_bench_yardstick.py`` checks this).

The result is a plain CSR: symmetric, de-duplicated, without self loops.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class HostGraph:
    """Undirected graph in CSR form, with features, labels and splits."""

    indptr: np.ndarray      # (n+1,) int64
    indices: np.ndarray     # (nnz,) int32, both directions of every edge
    x: np.ndarray           # (n, dx) float32
    y: np.ndarray           # (n,) int32
    train_mask: np.ndarray  # (n,) bool
    val_mask: np.ndarray    # (n,) bool
    test_mask: np.ndarray   # (n,) bool

    @property
    def num_nodes(self) -> int:
        return int(self.indptr.shape[0] - 1)

    @property
    def num_edges(self) -> int:
        return int(self.indices.shape[0])


def _sbm_edges(n: int, k: int, comm: np.ndarray, avg_deg: float,
               p_in_frac: float, rng: np.random.Generator):
    """Expected-count edge sampling per community (intra and inter)."""
    deg_in = avg_deg * p_in_frac
    deg_out = avg_deg * (1 - p_in_frac)
    sizes = np.bincount(comm, minlength=k).astype(np.int64)
    starts = np.concatenate([[0], np.cumsum(sizes)])
    order = np.argsort(comm, kind="stable")
    srcs, dsts = [], []
    for a in range(k):
        na = sizes[a]
        if na < 2:
            continue
        m = rng.poisson(na * deg_in / 2.0)
        if m:
            srcs.append(order[starts[a] + rng.integers(0, na, m)])
            dsts.append(order[starts[a] + rng.integers(0, na, m)])
        m = rng.poisson(na * deg_out / 2.0)
        if m:
            srcs.append(order[starts[a] + rng.integers(0, na, m)])
            dsts.append(rng.integers(0, n, m))
    if not srcs:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    return np.concatenate(srcs), np.concatenate(dsts)


def _csr(n: int, src: np.ndarray, dst: np.ndarray):
    """Symmetric, de-duplicated, self-loop-free CSR of an edge list."""
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    keep = src != dst
    src, dst = src[keep], dst[keep]
    code = np.unique(np.concatenate([src * n + dst, dst * n + src]))
    a, b = code // n, code % n   # sorted by a, then b
    indptr = np.zeros(n + 1, np.int64)
    np.add.at(indptr, a + 1, 1)
    return np.cumsum(indptr), b.astype(np.int32)


def make_graph(spec: dict) -> HostGraph:
    """Build the graph a configuration's ``graph`` block describes.

    Keys: ``nodes``, ``avg_degree``, ``classes``, ``features``, ``seed``,
    ``p_in_frac``, ``feature_snr``, ``label_noise`` and ``splits``
    (train and validation shares).
    """
    n, k, dx = int(spec["nodes"]), int(spec["classes"]), int(spec["features"])
    rng = np.random.default_rng(int(spec["seed"]))
    comm = rng.integers(0, k, n).astype(np.int32)
    src, dst = _sbm_edges(n, k, comm, float(spec["avg_degree"]),
                          float(spec["p_in_frac"]), rng)
    centroids = rng.normal(0.0, 1.0, (k, dx)).astype(np.float32)
    centroids *= float(spec["feature_snr"]) / np.sqrt(dx)
    x = centroids[comm] + rng.normal(0, 1.0 / np.sqrt(dx), (n, dx)).astype(
        np.float32)
    y = comm.copy()
    flip = rng.random(n) < float(spec["label_noise"])
    y[flip] = rng.integers(0, k, int(flip.sum()))
    perm = rng.permutation(n)
    n_train = int(spec["splits"][0] * n)
    n_val = int(spec["splits"][1] * n)
    masks = [np.zeros(n, bool) for _ in range(3)]
    masks[0][perm[:n_train]] = True
    masks[1][perm[n_train:n_train + n_val]] = True
    masks[2][perm[n_train + n_val:]] = True
    indptr, indices = _csr(n, src, dst)
    return HostGraph(indptr=indptr, indices=indices, x=x,
                     y=y.astype(np.int32), train_mask=masks[0],
                     val_mask=masks[1], test_mask=masks[2])
