"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows and writes one machine-readable
``experiments/bench/BENCH_<name>.json`` artifact per benchmark ({name,
backend, rows: {entry: {us_per_call, ...}}}) so the perf trajectory stays
trackable across PRs. Run:
    PYTHONPATH=src python -m benchmarks.run [--only NAME] [--fast]
                                           [--backend {segment,ell}]

Paper mapping (DESIGN.md §6):
  bench_grad_error            -> Fig 3   (relative mini-batch gradient error)
  bench_convergence_speed     -> Tbl 2 / Fig 2 (steps & time to target acc)
  bench_batch_size_robustness -> Tbl 3   (accuracy vs clusters per batch)
  bench_ablation_compensation -> Fig 4 / Tbl 8-9 (C_f / C_b / β)
  bench_time_per_epoch        -> App E.2 (per-epoch wall time by method)
  bench_message_retention     -> Tbl 7   (% adjacency retained fwd/bwd)
  bench_spider                -> App F   (variance-reduced estimator)
  bench_spmm_kernel           -> kernel hot-spot micro-benchmark
  bench_compensate            -> Eq. 9/12 fused gather+lerp micro-benchmark
  bench_pipeline              -> async sampling pipeline + minibatch
                                 recycling (DESIGN.md §9): sync-vs-prefetch
                                 step times, overlap fraction, ρ=4 parity
  bench_supervisor            -> training-supervisor overhead (DESIGN.md
                                 §10): guarded-vs-unguarded step medians,
                                 sync-vs-async checkpoint save cost
  bench_backends              -> segment vs ell vs ti head-to-head
                                 (DESIGN.md §11): convergence at fixed step
                                 counts + per-step historical-store traffic;
                                 the ti step must stay <= 1.0x the ell step
  bench_serve                 -> serving tier (DESIGN.md §12): client p50/
                                 p99 + throughput across QPS x fault-rate,
                                 degraded-rung parity, drain accounting
"""
from __future__ import annotations

import argparse
import inspect
import json
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
OUT = ROOT / "experiments" / "bench"


def _timer(fn, iters=3):
    """Best-of-iters per-call time in us (min is the noise-robust estimator
    for microbenchmarks — the perf tripwire in scripts/check.sh compares
    these numbers across runs, so jitter must not read as regression)."""
    fn()  # warmup/compile
    best = float("inf")
    for _ in range(iters):
        t0 = time.time()
        fn()
        best = min(best, time.time() - t0)
    return best * 1e6  # us


def _setup(preset="ppi-cpu", hidden=64, layers=3, parts=16, seed=0):
    import jax
    from repro.core import from_graph
    from repro.graph import make_sbm_dataset, partition_graph
    from repro.models import make_gnn
    g = make_sbm_dataset(preset, seed=3)
    data = from_graph(g)
    gnn = make_gnn("gcn", g.feature_dim, hidden, g.num_classes, layers)
    params = gnn.init_params(jax.random.key(seed))
    pts = partition_graph(g, parts, seed=0)
    return g, data, gnn, params, pts


# ------------------------------------------------------------------- Fig 3
def bench_grad_error(fast=False):
    import jax
    import jax.numpy as jnp
    from repro.core import (METHODS, backward_sgd_grads, exact_layer_values,
                            full_grads, init_history, make_train_step,
                            to_device_batch)
    from repro.graph import ClusterSampler
    g, data, gnn, params, parts = _setup()
    hs, vs = exact_layer_values(gnn, params, data)
    _, gfull = full_grads(gnn, params, data)

    def rel(ga, gb):
        f1, f2 = jax.tree.leaves(ga), jax.tree.leaves(gb)
        num = sum(float(jnp.sum((a - b) ** 2)) for a, b in zip(f1, f2))
        den = sum(float(jnp.sum(jnp.asarray(b) ** 2)) for b in f2)
        return (num / max(den, 1e-12)) ** 0.5

    rows = {}
    for name in ("lmc", "gas", "cluster", "cf_only", "cb_only"):
        m = METHODS[name]
        s = ClusterSampler(g, 16, 2, parts=parts, seed=1,
                           include_halo=m.include_halo,
                           edge_weight_mode=m.edge_weight_mode,
                           stochastic=False)
        step = jax.jit(make_train_step(gnn, m, g.num_nodes))
        store = init_history(gnn.num_layers, g.num_nodes, gnn.hidden_dim)
        for _ in range(2 if fast else 4):
            for sg in s.epoch():
                _, _, store, _ = step(params, store, to_device_batch(sg),
                                      data.x, data.self_w)
        bias, err = [], []
        t0 = time.time()
        n = 0
        for sg in s.epoch():
            _, gm, store, _ = step(params, store, to_device_batch(sg),
                                   data.x, data.self_w)
            nodes = jnp.asarray(sg.batch_gids[sg.batch_mask > 0])
            gsgd = backward_sgd_grads(gnn, params, data, hs, vs, nodes,
                                      scale=8.0)
            bias.append(rel(gm["layers"], gsgd))
            err.append(rel(gm, gfull))
            n += 1
        us = (time.time() - t0) / n * 1e6
        rows[name] = {"bias": float(np.mean(bias)),
                      "full_err": float(np.mean(err))}
        print(f"grad_error/{name},{us:.0f},bias={np.mean(bias):.4f};"
              f"err_vs_full={np.mean(err):.4f}", flush=True)
    assert rows["lmc"]["bias"] < rows["gas"]["bias"] < rows["cluster"]["bias"]
    return rows


# ----------------------------------------------------------- Tbl 2 / Fig 2
def bench_convergence_speed(fast=False):
    from repro.core import METHODS
    from repro.graph import ClusterSampler
    from repro.optim import sgd
    from repro.train import GNNTrainer
    g, data, gnn, params, parts = _setup(hidden=64, layers=2)
    target = 0.60
    steps_budget = 150 if fast else 400
    rows = {}
    for name in ("lmc", "gas", "cluster"):
        m = METHODS[name]
        steps, times = [], []
        for seed in range(1 if fast else 3):
            s = ClusterSampler(g, 16, 2, parts=parts, seed=seed,
                               include_halo=m.include_halo,
                               edge_weight_mode=m.edge_weight_mode)
            tr = GNNTrainer(gnn, m, g, s, sgd(lr=0.3), seed=seed)
            t0 = time.time()
            steps_to_target = steps_budget
            for _ in range(steps_budget // 25):
                tr.run(25)
                if float(tr.eval("val")) >= target:
                    steps_to_target = tr.step_num
                    break
            steps.append(steps_to_target)
            times.append(time.time() - t0)
        rows[name] = float(np.mean(steps))
        print(f"convergence/{name},{np.mean(times)*1e6:.0f},"
              f"steps_to_{target}acc={np.mean(steps):.0f}", flush=True)
    return rows


# ------------------------------------------------------------------- Tbl 3
def bench_batch_size_robustness(fast=False):
    from repro.core import GAS, LMC
    from repro.graph import ClusterSampler
    from repro.optim import sgd
    from repro.train import GNNTrainer
    g, data, gnn, params, parts = _setup(hidden=64, layers=2)
    rows = {}
    for c in ([1, 4] if fast else [1, 2, 4]):
        for m in (LMC, GAS):
            s = ClusterSampler(g, 16, c, parts=parts, seed=0,
                               include_halo=m.include_halo,
                               edge_weight_mode=m.edge_weight_mode)
            tr = GNNTrainer(gnn, m, g, s, sgd(lr=0.3), seed=0)
            t0 = time.time()
            tr.run(100 if fast else 200)
            acc = float(tr.eval("test"))
            rows[f"{m.name}_c{c}"] = acc
            print(f"batch_robustness/{m.name}_c{c},"
                  f"{(time.time()-t0)*1e6:.0f},test_acc={acc:.4f}", flush=True)
    return rows


# ----------------------------------------------------------- Fig 4 / Tbl 8
def bench_ablation_compensation(fast=False):
    from repro.core import METHODS
    from repro.graph import ClusterSampler
    from repro.optim import sgd
    from repro.train import GNNTrainer
    g, data, gnn, params, parts = _setup(hidden=64, layers=2)
    rows = {}
    for name in ("lmc", "cf_only", "cb_only", "gas"):
        m = METHODS[name]
        s = ClusterSampler(g, 16, 1, parts=parts, seed=0,  # small batch
                           include_halo=m.include_halo,
                           edge_weight_mode=m.edge_weight_mode)
        tr = GNNTrainer(gnn, m, g, s, sgd(lr=0.3), seed=0)
        t0 = time.time()
        tr.run(100 if fast else 250)
        acc = float(tr.eval("val"))
        rows[name] = acc
        print(f"ablation/{name},{(time.time()-t0)*1e6:.0f},"
              f"val_acc={acc:.4f}", flush=True)
    return rows


# --------------------------------------------------------------- App E.2
def bench_time_per_epoch(fast=False, backend="segment"):
    import jax
    from repro.core import (METHODS, init_history, make_train_step,
                            to_device_batch)
    from repro.graph import ClusterSampler
    g, data, gnn, params, parts = _setup()
    rows = {}
    for name in ("lmc", "gas", "cluster"):
        m = METHODS[name]
        s = ClusterSampler(g, 16, 2, parts=parts, seed=0,
                           include_halo=m.include_halo,
                           edge_weight_mode=m.edge_weight_mode)
        step = jax.jit(make_train_step(gnn, m, g.num_nodes, backend=backend))
        store = init_history(gnn.num_layers, g.num_nodes, gnn.hidden_dim)
        batches = [to_device_batch(sg, backend=backend) for sg in s.epoch()]

        def epoch():
            nonlocal store
            for b in batches:
                _, _, store, _ = step(params, store, b, data.x, data.self_w)
            jax.block_until_ready(store.h)

        us = _timer(epoch, iters=2 if fast else 4)
        rows[f"{name}_{backend}"] = {"us_per_call": us, "backend": backend}
        print(f"time_per_epoch/{name}_{backend},{us:.0f},epoch_s={us/1e6:.3f}",
              flush=True)
    return rows


# ------------------------------------------------------------------- Tbl 7
def bench_message_retention(fast=False):
    """% of whole-graph messages retained in fwd/bwd per method (Tbl 7)."""
    from repro.core import METHODS
    from repro.graph import ClusterSampler
    g, data, gnn, params, parts = _setup()
    total = g.num_edges
    rows = {}
    for name in ("lmc", "gas", "cluster"):
        m = METHODS[name]
        s = ClusterSampler(g, 16, 2, parts=parts, seed=0,
                           include_halo=m.include_halo,
                           edge_weight_mode=m.edge_weight_mode,
                           stochastic=False)
        # paper Tbl 7: fraction of Ã entries participating at least once per
        # epoch; GAS's backward only propagates adjoints along batch-internal
        # edges, LMC compensates the rest (100% like full-batch GD)
        fwd_edges, bwd_edges = set(), set()
        t0 = time.time()
        for sg in s.epoch():
            gids = np.concatenate([sg.batch_gids, sg.halo_gids])
            ne = sg.n_edges_real
            su = gids[sg.edge_src[:ne]].astype(np.int64)
            dv = gids[sg.edge_dst[:ne]].astype(np.int64)
            code = su * g.num_nodes + dv
            fwd_edges.update(code.tolist())
            if name == "lmc":
                bwd_edges.update(code.tolist())
            else:
                nb = sg.batch_gids.shape[0]
                intra = (sg.edge_src[:ne] < nb) & (sg.edge_dst[:ne] < nb)
                bwd_edges.update(code[intra].tolist())
        us = (time.time() - t0) * 1e6
        rows[name] = {"us_per_call": us, "fwd": len(fwd_edges) / total,
                      "bwd": len(bwd_edges) / total}
        print(f"message_retention/{name},{us:.0f},"
              f"fwd={len(fwd_edges)/total:.2%};bwd={len(bwd_edges)/total:.2%}",
              flush=True)
    return rows


# --------------------------------------------------------------------- App F
def bench_spider(fast=False):
    """LMC-SPIDER: the anchored running estimate has lower error than the
    plain per-batch estimate at equal small-batch cost (App. F)."""
    import jax
    import jax.numpy as jnp
    from repro.core import (LMC, full_grads, init_history, make_train_step,
                            to_device_batch)
    from repro.graph import ClusterSampler
    from repro.optim import make_spider_controller
    g, data, gnn, params, parts = _setup(hidden=32, layers=2)
    s = ClusterSampler(g, 16, 2, parts=parts, seed=0)
    step = jax.jit(make_train_step(gnn, LMC, g.num_nodes))
    store = init_history(gnn.num_layers, g.num_nodes, gnn.hidden_dim)
    for _ in range(2):
        for sg in s.epoch():
            _, _, store, _ = step(params, store, to_device_batch(sg),
                                  data.x, data.self_w)
    _, gfull = full_grads(gnn, params, data)

    def rel(ga):
        f1, f2 = jax.tree.leaves(ga), jax.tree.leaves(gfull)
        num = sum(float(jnp.sum((a - b) ** 2)) for a, b in zip(f1, f2))
        den = sum(float(jnp.sum(jnp.asarray(b) ** 2)) for b in f2)
        return (num / max(den, 1e-12)) ** 0.5

    init, _, anchor, refine = make_spider_controller(q=4)
    sa = ClusterSampler(g, 16, 8, parts=parts, seed=3)   # large anchor batch
    t0 = time.time()
    _, g_anchor, store, _ = step(params, store, to_device_batch(sa.sample()),
                                 data.x, data.self_w)
    st = anchor(init(params), params, g_anchor)
    plain_errs, spider_errs = [], []
    for _ in range(4 if fast else 8):
        sg = s.sample()
        _, g_small, store, _ = step(params, store, to_device_batch(sg),
                                    data.x, data.self_w)
        plain_errs.append(rel(g_small))
        # fixed params: the SPIDER difference term cancels exactly, so the
        # estimate stays anchored at the large-batch gradient
        st = refine(st, params, g_small, g_small)
        spider_errs.append(rel(st.g_est))
    us = (time.time() - t0) * 1e6
    print(f"spider,{us:.0f},plain_err={np.mean(plain_errs):.4f};"
          f"spider_err={np.mean(spider_errs):.4f}", flush=True)
    assert np.mean(spider_errs) < np.mean(plain_errs)
    return {"spider": {"us_per_call": us,
                       "plain_err": float(np.mean(plain_errs)),
                       "spider_err": float(np.mean(spider_errs))}}


# ----------------------------------------------------------------- kernels
def bench_spmm_kernel(fast=False):
    import jax
    import jax.numpy as jnp
    from repro.kernels import build_ell, bucketed_spmm, default_interpret
    from repro.kernels.ops import _build_ell_loop
    from repro.kernels.ref import degree_bucket_spmm_ref
    g, data, gnn, params, parts = _setup()
    row = np.repeat(np.arange(g.num_nodes), np.diff(g.indptr))
    ws = g.gcn_edge_weights(g.indices.astype(np.int64), row)
    ell = build_ell(g.indptr, g.indices, ws)
    h = jnp.asarray(np.random.default_rng(0).normal(
        size=(g.num_nodes, 128)).astype(np.float32))
    ptr, ind, wj = (jnp.asarray(g.indptr), jnp.asarray(g.indices),
                    jnp.asarray(ws))
    ref = jax.jit(lambda h_: degree_bucket_spmm_ref(ptr, ind, wj, h_))
    # identical protocol for all paths: _timer warms up (compile/trace) then
    # takes best-of-iters over the same number of steady-state iterations
    iters = 3 if fast else 5
    us_ref = _timer(lambda: jax.block_until_ready(ref(h)), iters=iters)
    us_str = _timer(lambda: jax.block_until_ready(
        bucketed_spmm(ell, h)), iters=iters)
    nnz = g.num_edges
    gflops = lambda us: 2 * nnz * 128 / us / 1e3
    mode = "interpret" if default_interpret() else "compiled"
    rows = {
        "jnp_segment_sum": {"us_per_call": us_ref, "gflops": gflops(us_ref)},
        f"pallas_{mode}_streamed": {"us_per_call": us_str,
                                    "gflops": gflops(us_str),
                                    "default_path": True},
    }
    print(f"spmm/jnp_segment_sum,{us_ref:.0f},gflops={gflops(us_ref):.2f}",
          flush=True)
    note = (";note=interpret-mode;TPU-target-not-CPU-representative"
            if mode == "interpret" else "")
    print(f"spmm/pallas_{mode}_streamed,{us_str:.0f},"
          f"gflops={gflops(us_str):.2f}{note}", flush=True)

    # ELL preprocessing: vectorized bulk-numpy builder vs the original
    # per-node Python loop, on a 50k-node synthetic CSR graph
    rng = np.random.default_rng(1)
    n50, avg_deg = 50_000, 10
    e50 = n50 * avg_deg
    dst = np.sort(rng.integers(0, n50, e50))
    indptr50 = np.zeros(n50 + 1, np.int64)
    indptr50[1:] = np.cumsum(np.bincount(dst, minlength=n50))
    indices50 = rng.integers(0, n50, e50).astype(np.int32)
    ws50 = rng.random(e50).astype(np.float32)
    t0 = time.time()
    _build_ell_loop(indptr50, indices50, ws50)
    us_loop = (time.time() - t0) * 1e6
    us_vec = _timer(lambda: build_ell(indptr50, indices50, ws50,
                                      with_transpose=False), iters=iters)
    speedup = us_loop / us_vec
    rows["build_ell_loop_50k"] = {"us_per_call": us_loop}
    rows["build_ell_vectorized_50k"] = {"us_per_call": us_vec,
                                        "speedup_vs_loop": speedup}
    print(f"spmm/build_ell_loop_50k,{us_loop:.0f},n=50000", flush=True)
    print(f"spmm/build_ell_vectorized_50k,{us_vec:.0f},"
          f"speedup_vs_loop={speedup:.1f}x", flush=True)
    if speedup < 10.0:
        # don't abort the harness (artifacts must still be written for the
        # remaining benches); scripts/check.sh enforces the tripwire
        print(f"# WARNING: vectorized build_ell only {speedup:.1f}x faster "
              f"than the loop (expected >= 10x)", flush=True)
    return rows


def bench_compensate(fast=False):
    """Fused LMC compensate (Eq. 9/12) micro-benchmark: jnp oracle vs the
    Pallas kernel (HBM→VMEM DMA store gather), plus a kernel run at 4x the
    ~24k-row cap a VMEM-resident store block would have. Same protocol as
    bench_spmm_kernel (warmup + equal steady-state iters); derived metric is
    effective GB/s over the gather+lerp traffic (store row reads + fresh
    reads + writes)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import default_interpret, lmc_compensate
    from repro.kernels.ref import lmc_compensate_ref

    rng = np.random.default_rng(0)
    n, d = 4096, 128                       # halo rows x hidden (train-scale)
    iters = 3 if fast else 5
    mode = "interpret" if default_interpret() else "compiled"
    rows = {}

    def one(entry, m, kernel):
        store = jnp.asarray(rng.normal(size=(m, d)).astype(np.float32))
        gids = jnp.asarray(rng.integers(0, m, n).astype(np.int32))
        beta = jnp.asarray(rng.random(n).astype(np.float32))
        mask = jnp.asarray((rng.random(n) > 0.2).astype(np.float32))
        fresh = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
        fn = jax.jit(lmc_compensate if kernel else lmc_compensate_ref)
        us = _timer(lambda: jax.block_until_ready(
            fn(store, gids, beta, fresh, mask)), iters=iters)
        gbps = 3 * n * d * 4 / us / 1e3    # store-gather + fresh + out bytes
        rows[entry] = {"us_per_call": us, "gbps": gbps, "store_rows": m}
        print(f"compensate/{entry},{us:.0f},gbps={gbps:.2f};m={m}", flush=True)

    m_small = 16384
    one("jnp_oracle", m_small, kernel=False)
    one(f"pallas_{mode}_streamed", m_small, kernel=True)
    # full-graph-scale store, past what a VMEM-resident block could hold
    one(f"pallas_{mode}_streamed_4xcap", 4 * 24576, kernel=True)
    rows[f"pallas_{mode}_streamed"]["default_path"] = True
    return rows


from benchmarks.bench_backends import bench_backends  # noqa: E402
from benchmarks.bench_pipeline import bench_pipeline  # noqa: E402
from benchmarks.bench_serve import bench_serve  # noqa: E402
from benchmarks.bench_supervisor import bench_supervisor  # noqa: E402
from repro.compile_cache import use_compile_cache  # noqa: E402

BENCHES = {
    "grad_error": bench_grad_error,
    "convergence_speed": bench_convergence_speed,
    "batch_size_robustness": bench_batch_size_robustness,
    "ablation_compensation": bench_ablation_compensation,
    "time_per_epoch": bench_time_per_epoch,
    "message_retention": bench_message_retention,
    "spider": bench_spider,
    "spmm_kernel": bench_spmm_kernel,
    "compensate": bench_compensate,
    "pipeline": bench_pipeline,
    "supervisor": bench_supervisor,
    "backends": bench_backends,
    "serve": bench_serve,
}


def main() -> None:
    use_compile_cache()
    import jax

    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--backend", default="segment",
                    choices=["segment", "ell", "ti"],
                    help="aggregation hot path for train-step benches")
    args = ap.parse_args()
    OUT.mkdir(parents=True, exist_ok=True)
    names = [args.only] if args.only else list(BENCHES)
    print("name,us_per_call,derived")
    for n in names:
        fn = BENCHES[n]
        kw = {"fast": args.fast}
        if "backend" in inspect.signature(fn).parameters:
            kw["backend"] = args.backend
        rows = fn(**kw)
        artifact = {"name": n, "backend": jax.default_backend(),
                    "agg_backend": kw.get("backend", "segment"),
                    "rows": rows or {}}
        # the kernel bench is the cross-PR perf tripwire: short stable name
        path = OUT / {"spmm_kernel": "BENCH_spmm.json"}.get(n,
                                                            f"BENCH_{n}.json")
        path.write_text(json.dumps(artifact, indent=2, sort_keys=True))
        print(f"# wrote {path.relative_to(ROOT)}", flush=True)


if __name__ == "__main__":
    main()
