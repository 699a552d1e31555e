"""End-to-end training driver: LMC-GCNII on a full-scale synthetic dataset
with checkpointing, fault tolerance, the Pallas-kernel aggregation path and
periodic evaluation — the production loop the paper's Table 1/2 workflow maps
onto.

    PYTHONPATH=src python examples/train_gnn.py --steps 400 --preset arxiv-cpu
    PYTHONPATH=src python examples/train_gnn.py --preset arxiv-like   # 169k nodes
    PYTHONPATH=src python examples/train_gnn.py --backend ell  # Pallas SpMM/
        # compensate kernels on the hot path (compiled on TPU, interpreted on CPU)
    PYTHONPATH=src python examples/train_gnn.py --backend ti
        # store-free message-invariance compensation (zero historical-store
        # reads/writes on the hot path; DESIGN.md §11)
    PYTHONPATH=src python examples/train_gnn.py --prefetch 4 --recycle 4
        # async sampling pipeline + minibatch recycling (DESIGN.md §9)
    PYTHONPATH=src python examples/train_gnn.py --health --async-ckpt
        # numerical-health supervisor (NaN/spike guard with rollback) +
        # background checkpoint writes (DESIGN.md §10)
"""
import argparse
import time

from repro.compile_cache import use_compile_cache
from repro.core import METHODS
from repro.graph import ClusterSampler, make_sbm_dataset, partition_graph
from repro.models import make_gnn
from repro.optim import sgd
from repro.train import GNNTrainer, HealthConfig


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser(
        description="End-to-end LMC GNN training on a synthetic full-scale "
                    "dataset (checkpointing, fault tolerance, Pallas kernel "
                    "path, async sampling pipeline)")
    ap.add_argument("--steps", type=int, default=400,
                    help="total train steps (resumes from checkpoint if any)")
    ap.add_argument("--preset", default="arxiv-cpu",
                    help="synthetic dataset preset, e.g. arxiv-cpu (4k nodes) "
                         "or arxiv-like (169k); see repro.graph.synthetic."
                         "DATASET_PRESETS")
    ap.add_argument("--arch", default="gcnii", choices=["gcn", "gcnii",
                                                        "sage", "gin"],
                    help="GNN architecture")
    ap.add_argument("--method", default=None, choices=list(METHODS),
                    help="mini-batch method: lmc, gas, cluster, ti, or the "
                         "compensation ablations (default: ti when "
                         "--backend ti, else lmc)")
    ap.add_argument("--hidden", type=int, default=128,
                    help="hidden width of every GNN layer")
    ap.add_argument("--layers", type=int, default=4,
                    help="number of GNN layers")
    ap.add_argument("--parts", type=int, default=32,
                    help="graph partition count B (clusters)")
    ap.add_argument("--clusters-per-batch", type=int, default=4,
                    help="clusters c sampled per mini-batch (Alg. 1 line 4)")
    ap.add_argument("--backend", default="segment",
                    choices=["segment", "ell", "ti"],
                    help="aggregation/compensation hot path: jnp segment-sum, "
                         "the Pallas bucketed-ELL SpMM/compensate kernels "
                         "(compiled on TPU, interpreter fallback on CPU), or "
                         "ti = ELL aggregation + store-free message-"
                         "invariance compensation (zero historical-store "
                         "reads; DESIGN.md §11)")
    ap.add_argument("--prefetch", type=int, default=2, metavar="N",
                    help="async sampling pipeline queue depth: background "
                         "threads build + bucket the next N batches while "
                         "the device steps, with double-buffered "
                         "host->device transfer (DESIGN.md §9); 0 keeps the "
                         "schedule-indexed stream but builds synchronously")
    ap.add_argument("--recycle", type=int, default=1, metavar="R",
                    help="minibatch recycling: reuse each sampled subgraph "
                         "for R consecutive steps before resampling "
                         "(LazyGNN-style; staleness stays within LMC's "
                         "Thm 2 bound — see DESIGN.md §9)")
    ap.add_argument("--pipeline-workers", type=int, default=2, metavar="W",
                    help="builder threads for the sampling pipeline")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_gnn_ckpt",
                    help="checkpoint directory (delete it for a fresh run)")
    ap.add_argument("--health", action="store_true",
                    help="enable the numerical-health guard (NaN/Inf + "
                         "loss-spike checks with staleness accounting, "
                         "DESIGN.md §10)")
    ap.add_argument("--health-policy", default="rollback",
                    choices=["rollback", "skip-batch"],
                    help="recovery policy on a divergent step: roll back to "
                         "the newest verifiable checkpoint, or drop the "
                         "poisoned update and continue")
    ap.add_argument("--lr-backoff", type=float, default=1.0, metavar="F",
                    help="multiply the lr by F on every health rollback "
                         "(1.0 = keep lr)")
    ap.add_argument("--max-retries", type=int, default=3, metavar="N",
                    help="consecutive recovery actions (rollbacks / skips / "
                         "pipeline rebuilds) allowed before the run aborts "
                         "with TrainingDivergedError")
    ap.add_argument("--async-ckpt", action="store_true",
                    help="write checkpoints on a background thread (the "
                         "train step only pays the device->host snapshot; "
                         "files are byte-identical to synchronous saves)")
    args = ap.parse_args()

    t0 = time.time()
    g = make_sbm_dataset(args.preset, seed=0)
    parts = partition_graph(g, args.parts, seed=0)
    print(f"[{time.time()-t0:6.1f}s] graph {g.num_nodes}n/{g.num_edges}e, "
          f"partitioned into {args.parts}")

    if args.method is None:
        args.method = "ti" if args.backend == "ti" else "lmc"
    m = METHODS[args.method]
    gnn = make_gnn(args.arch, g.feature_dim, args.hidden, g.num_classes,
                   args.layers)
    sampler = ClusterSampler(g, args.parts, args.clusters_per_batch,
                             parts=parts, seed=1,
                             include_halo=m.include_halo,
                             edge_weight_mode=m.edge_weight_mode)
    health = (HealthConfig(policy=args.health_policy,
                           lr_backoff=args.lr_backoff)
              if args.health else None)
    tr = GNNTrainer(gnn, m, g, sampler, sgd(lr=0.2), seed=0,
                    ckpt_dir=args.ckpt_dir, ckpt_every=100,
                    backend=args.backend,
                    prefetch=args.prefetch, recycle=args.recycle,
                    pipeline_workers=args.pipeline_workers,
                    health=health, max_retries=args.max_retries,
                    async_ckpt=args.async_ckpt)
    if tr.restore():
        print(f"resumed from checkpoint at step {tr.step_num}")

    while tr.step_num < args.steps:
        tr.run(50)
        h = tr.history[-1]
        print(f"[{time.time()-t0:6.1f}s] step {tr.step_num:5d} "
              f"loss {h['loss']:.4f} train_acc {h['train_acc']:.3f} "
              f"val {float(tr.eval('val')):.3f}")
    tr.save()
    tr.close()   # stop pipeline workers
    print(f"done: test acc {float(tr.eval('test')):.4f}; "
          f"checkpoints in {args.ckpt_dir}")


if __name__ == "__main__":
    main()
