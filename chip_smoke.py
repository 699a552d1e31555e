#!/usr/bin/env python3
"""Smoke run of LMC training on a TPU, through the entry points a user calls.

    python3 chip_smoke.py             # one chip: train, ell-vs-segment, ti
    python3 chip_smoke.py --chips 4   # only the sharded LMC step, four chips

Everything runs in this one process: a chip belongs to one process at a
time. There is no CPU fallback — off the TPU the script exits non-zero
before doing any work, so no phase ever runs Pallas in interpret mode.

One chip, on the full-size ``arxiv-like`` preset (169,343 nodes, seed 0)
partitioned into 32 parts, GCNII with hidden 128 and 4 layers, 4 clusters per
batch (the ``examples/train_gnn.py`` defaults):

  1. train — ``GNNTrainer(backend="ell", prefetch=2)`` takes a few steps with
     no checkpoint directory; every loss must be finite;
  2. ell vs segment — one batch through AOT-compiled ``ell`` and ``segment``
     steps under ``jax.default_matmul_precision("highest")``, from the trained
     params and store. The compiled ell step must contain ``tpu_custom_call``
     (the Pallas kernels compiled, not interpreted), and its loss, gradients
     and new store must match the segment step's within ``REL_TOL``;
  3. ti — a couple of ``GNNTrainer(backend="ti")`` steps, finite losses.

``--chips 4`` runs only the distributed LMC step (``core/distributed.py``):
four one-cluster batches stacked into one, sharded over a four-chip ``data``
mesh, against the same batch on one chip (loss, gradients and store).

Compile seconds, step seconds and peak device memory are printed on earlier
lines; the last line of stdout is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

PRESET = "arxiv-like"
PARTS = 32
CLUSTERS_PER_BATCH = 4
ARCH, HIDDEN, LAYERS = "gcnii", 128, 4
TRAIN_STEPS = 4          # ell steps: one with the compile, three steady
# Largest |ell - segment| (or |sharded - single|) allowed, relative to the
# largest magnitude of the same array. Both sides are f32 at "highest"
# precision and differ only in summation order (~1e-6); one dropped row or a
# neighbour list read from the wrong bucket moves that row by a whole
# message, at least ~1e-2 of the array's scale.
REL_TOL = 1e-4


def log(msg: str) -> None:
    print(msg, flush=True)


def tpu_devices(chips: int) -> list:
    """The first `chips` TPU devices; exits non-zero if there are none."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {devs[0].platform}")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def max_diffs(got, want) -> tuple[float, float]:
    """(max |got - want|, max over leaves of that / max |want|)."""
    worst_abs = worst_rel = 0.0
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        diff = float(jax.numpy.max(jax.numpy.abs(a - b)))
        scale = float(jax.numpy.max(jax.numpy.abs(b)))
        worst_abs = max(worst_abs, diff)
        worst_rel = max(worst_rel, diff / scale if scale > 0 else diff)
    return worst_abs, worst_rel


def check_close(what: str, got, want) -> None:
    abs_d, rel_d = max_diffs(got, want)
    log(f"{what}: max abs diff {abs_d!r}, max rel diff {rel_d!r} "
        f"(tolerance {REL_TOL})")
    if not rel_d <= REL_TOL:
        raise AssertionError(f"{what}: relative difference {rel_d!r} exceeds "
                             f"{REL_TOL}")


def peak_memory(dev) -> str:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 2**30:.3f} GiB"


def build_problem(clusters_per_batch: int):
    from repro.core import LMC
    from repro.graph import ClusterSampler, make_sbm_dataset, partition_graph
    from repro.models import make_gnn

    t0 = time.time()
    g = make_sbm_dataset(PRESET, seed=0)
    parts = partition_graph(g, PARTS, seed=0)
    gnn = make_gnn(ARCH, g.feature_dim, HIDDEN, g.num_classes, LAYERS)
    sampler = ClusterSampler(g, PARTS, clusters_per_batch, parts=parts,
                             seed=1, include_halo=LMC.include_halo,
                             edge_weight_mode=LMC.edge_weight_mode)
    log(f"setup: {PRESET} {g.num_nodes} nodes / {g.num_edges} edges, "
        f"{PARTS} parts, {ARCH} hidden {HIDDEN} x {LAYERS} layers, "
        f"{clusters_per_batch} cluster(s) per batch, padded batch "
        f"{sampler.pad_batch} + halo {sampler.pad_halo} rows, "
        f"{sampler.pad_edges} edges; {time.time() - t0:.1f}s on the host")
    return g, gnn, sampler


def train(gnn, method, g, sampler, backend: str, steps: int):
    """A few GNNTrainer steps; returns the trainer (closed) after checks."""
    import numpy as np

    from repro.optim import sgd
    from repro.train import GNNTrainer

    tr = GNNTrainer(gnn, method, g, sampler, sgd(lr=0.2), seed=0,
                    backend=backend, prefetch=2)
    try:
        tr.run(steps)
    finally:
        tr.close()
    recs = [r for r in tr.history if "loss" in r]
    losses = [r["loss"] for r in recs]
    if len(recs) != steps or not np.all(np.isfinite(losses)):
        raise AssertionError(f"{backend}: history {tr.history}")
    times = [r["time_s"] for r in recs]
    log(f"train[{backend}]: {steps} steps, losses {losses}")
    log(f"train[{backend}]: first step {times[0]:.3f}s (includes compile), "
        f"later steps median {float(np.median(times[1:])):.3f}s "
        f"{[round(t, 3) for t in times[1:]]}")
    return tr


def one_chip() -> None:
    import jax.numpy as jnp

    from repro.core import LMC, TI, from_graph, host_batch, make_train_step

    dev = tpu_devices(1)[0]
    g, gnn, sampler = build_problem(CLUSTERS_PER_BATCH)

    # 1. the production loop on the Pallas path
    tr = train(gnn, LMC, g, sampler, "ell", TRAIN_STEPS)
    log(f"peak device memory after training: {peak_memory(dev)}")

    # 2. ell vs segment on one batch, from the trained params and store
    data = from_graph(g)
    batch = jax.device_put(host_batch(
        sampler.build_batch(sampler.clusters_at(TRAIN_STEPS)), backend="ell"))
    args = (tr.params, tr.store, batch, data.x, data.self_w)
    out = {}
    with jax.default_matmul_precision("highest"):
        for backend in ("ell", "segment"):
            step = jax.jit(make_train_step(gnn, LMC, g.num_nodes,
                                           backend=backend))
            t0 = time.time()
            compiled = step.lower(*args).compile()
            t_compile = time.time() - t0
            has_kernel = "tpu_custom_call" in compiled.as_text()
            if backend == "ell" and not has_kernel:
                raise AssertionError("compiled ell step has no "
                                     "tpu_custom_call: Pallas did not compile")
            jax.block_until_ready(compiled(*args))   # warm
            t0 = time.time()
            out[backend] = jax.block_until_ready(compiled(*args))
            log(f"step[{backend}, highest]: compile {t_compile:.3f}s, "
                f"step {time.time() - t0:.3f}s, tpu_custom_call {has_kernel}")
    (l_e, g_e, s_e, _), (l_s, g_s, s_s, _) = out["ell"], out["segment"]
    if not bool(jnp.isfinite(l_e)):
        raise AssertionError(f"ell loss {l_e}")
    check_close("ell vs segment loss", l_e, l_s)
    check_close("ell vs segment grads", g_e, g_s)
    check_close("ell vs segment store", s_e, s_s)
    del out, args, batch
    log(f"peak device memory after comparison: {peak_memory(dev)}")

    # 3. the store-free ti backend
    train(gnn, TI, g, sampler, "ti", 2)
    log(f"peak device memory: {peak_memory(dev)}")


def four_chips() -> None:
    import numpy as np
    import jax.numpy as jnp

    from repro.core import LMC, from_graph, init_history, make_train_step
    from repro.core.distributed import spmd_shardings, stack_batches
    from repro.core.history import HistoricalState
    from repro.dist.mesh import make_mesh
    from repro.dist.sharding import activation_sharding

    devs = tpu_devices(4)
    # one cluster per device (core/distributed.py), 4 clusters in all
    g, gnn, sampler = build_problem(1)
    clusters = np.random.default_rng(0).permutation(PARTS)[:len(devs)]
    t0 = time.time()
    flat = stack_batches([sampler.build_batch(np.array([c]))
                          for c in clusters])
    log(f"stacked batch of clusters {clusters.tolist()}: "
        f"{flat.batch_gids.shape[0]} batch + {flat.halo_gids.shape[0]} halo "
        f"rows, {flat.edge_src.shape[0]} edges; {time.time() - t0:.1f}s")

    # node-axis arrays shard by rows: pad the node count to the mesh size
    # (the pad rows are never gathered, and scatter_rows drops index n_pad)
    n_pad = -(-g.num_nodes // len(devs)) * len(devs)
    data = from_graph(g)
    x = jnp.pad(data.x, ((0, n_pad - g.num_nodes), (0, 0)))
    self_w = jnp.pad(data.self_w, (0, n_pad - g.num_nodes))
    params = gnn.init_params(jax.random.key(0))
    kh, kv = jax.random.split(jax.random.key(1))
    zeros = init_history(LAYERS, n_pad, HIDDEN)   # random, so halos matter
    store = HistoricalState(h=jax.random.normal(kh, zeros.h.shape),
                            v=jax.random.normal(kv, zeros.v.shape))
    step = make_train_step(gnn, LMC, n_pad)
    args = (params, store, flat, x, self_w)

    mesh = make_mesh((len(devs),), ("data",), devices=devs)
    bsh, ssh, xsh, swsh, psh = spmd_shardings(mesh, model_axis=None)
    shardings = (jax.tree.map(lambda _: psh, params),
                 HistoricalState(h=ssh["h"], v=ssh["v"]), bsh, xsh, swsh)
    sharded = jax.device_put(args, shardings)
    for name, arr in (("store.h", sharded[1].h), ("x", sharded[3]),
                      ("batch_gids", sharded[2].batch_gids),
                      ("edge_src", sharded[2].edge_src)):
        row_axis = 1 if name == "store.h" else 0
        shards = arr.addressable_shards
        rows = {s.data.shape[row_axis] for s in shards}
        if (len({s.device for s in shards}) != len(devs)
                or rows != {arr.shape[row_axis] // len(devs)}):
            placed = [(s.device, s.data.shape) for s in shards]
            raise AssertionError(f"{name} not spread over {len(devs)} chips: "
                                 f"{placed}")
    log(f"sharded inputs spread over {len(devs)} chips "
        f"({[d.id for d in devs]}), one row block each")

    with jax.default_matmul_precision("highest"):
        single = jax.jit(step)
        t0 = time.time()
        compiled = single.lower(*args).compile()
        log(f"single chip: compile {time.time() - t0:.3f}s")
        ref = jax.block_until_ready(compiled(*args))
        with activation_sharding(mesh):
            spmd = jax.jit(step, in_shardings=shardings)
            t0 = time.time()
            compiled = spmd.lower(*sharded).compile()
            log(f"{len(devs)} chips: compile {time.time() - t0:.3f}s")
            got = jax.block_until_ready(compiled(*sharded))
        t0 = time.time()
        jax.block_until_ready(compiled(*sharded))
        log(f"{len(devs)} chips: step {time.time() - t0:.3f}s")
    out_store = got[2].h
    if len(out_store.sharding.device_set) != len(devs):
        raise AssertionError(f"output store on {out_store.sharding}")
    if not bool(jnp.isfinite(got[0])):
        raise AssertionError(f"sharded loss {got[0]}")
    check_close("sharded vs single loss", got[0], ref[0])
    check_close("sharded vs single grads", got[1], ref[1])
    check_close("sharded vs single store", got[2], ref[2])
    for d in devs:
        log(f"peak device memory {d.id}: {peak_memory(d)}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded LMC step on four chips")
    args = ap.parse_args()
    tpu_devices(args.chips)

    from repro.compile_cache import use_compile_cache

    log(f"compile cache: {use_compile_cache()}")
    t0 = time.time()
    if args.chips == 4:
        four_chips()
    else:
        one_chip()
    log(f"all phases passed in {time.time() - t0:.1f}s")
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
