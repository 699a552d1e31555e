"""Quickstart: LMC vs GAS vs Cluster-GCN on a synthetic ogbn-arxiv-like graph.

Trains the paper's GCN with each mini-batch method for a few hundred steps and
prints the validation-accuracy trajectory — the minimal version of the paper's
Figure 2.

    PYTHONPATH=src python examples/quickstart.py [--steps 300]
"""
import argparse

from repro.compile_cache import use_compile_cache
from repro.core import METHODS
from repro.graph import ClusterSampler, make_sbm_dataset, partition_graph
from repro.models import make_gnn
from repro.optim import sgd
from repro.train import GNNTrainer


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--preset", default="arxiv-cpu")
    args = ap.parse_args()

    g = make_sbm_dataset(args.preset, seed=0)
    parts = partition_graph(g, 32, seed=0)
    print(f"graph: {g.num_nodes} nodes, {g.num_edges} directed edges, "
          f"{g.num_classes} classes")

    for name in ("lmc", "gas", "cluster"):
        m = METHODS[name]
        gnn = make_gnn("gcn", g.feature_dim, 128, g.num_classes, 2)
        sampler = ClusterSampler(g, 32, 4, parts=parts, seed=1,
                                 include_halo=m.include_halo,
                                 edge_weight_mode=m.edge_weight_mode)
        tr = GNNTrainer(gnn, m, g, sampler, sgd(lr=0.3), seed=0)
        print(f"\n=== {name} ===")
        for k in range(args.steps // 50):
            tr.run(50)
            print(f"  step {tr.step_num:4d}  "
                  f"loss {tr.history[-1]['loss']:.3f}  "
                  f"val acc {float(tr.eval('val')):.3f}")
        print(f"  final test acc: {float(tr.eval('test')):.3f}")


if __name__ == "__main__":
    main()
