#!/usr/bin/env python3
"""Readings of the comparison's control and planted faults, for the limits.

    python3 bench/control.py --workload gcnii-flickr.segment --seeds 11 12 13

For each seed it takes the cell's three reference steps (float32, precision
``highest``) and puts in the program's place, at the cell's own sizes:

* ``control``: the reference with every matrix product in three bfloat16
  passes (``dot_bf16x3``), the precision just below the one stated;
* ``half_batch``: the reference with every second labelled batch row left
  out of the loss and the mean taken over the rest.

It prints one JSON line per seed and variant with the numbers
``compare.numbers`` reads. A step that returns its state unchanged needs no
run: it reads 1 on ``grad_gap``, ``change_gap`` and ``hbar_gap``. Where no
TPU is found it exits with code 3, unless ``--cpu`` is given (tests, tiny
sizes). This is not part of a benchmark run.
"""
import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

VARIANTS = {"control": ("bf16x3", None), "half_batch": ("highest",
                                                         "half_batch")}


def readings(root, workload: str, seeds, variants=tuple(VARIANTS)):
    """Yield ``{"seed", "variant", numbers...}`` for each seed and variant."""
    import jax

    from bench import compare, graphgen, harness
    from bench.reference import lmc as ref_lmc
    from repro.graph import ClusterSampler, partition_graph

    cell = harness.find_cell(Path(root), workload)
    cfg, tr = cell.config, cell.traffic
    arch = harness.reference_arch(cell)
    hg = graphgen.make_graph(cfg["graph"])
    graph = harness.program_graph(hg)
    parts = partition_graph(graph, tr["parts"], seed=0)
    consts = ref_lmc.graph_consts(hg)

    def stepper(prec, fault):
        return jax.jit(ref_lmc.make_step(
            arch, cfg, tr["parts"], tr["clusters_per_batch"],
            ref_lmc.PRECISIONS[prec], fault=fault))
    base = stepper("highest", None)
    others = {v: stepper(*VARIANTS[v]) for v in variants}
    for seed in seeds:
        sampler = ClusterSampler(graph, tr["parts"], tr["clusters_per_batch"],
                                 parts=parts, seed=int(seed),
                                 beta_spec=tuple(cfg["beta_score"]))
        w = jax.device_get(jax.jit(lambda k: arch.init_params(k, cfg))(
            harness.weight_key(seed)))
        args = (cfg, consts, parts, sampler, tr, w)
        ref = harness.run_reference(base, *args)
        for v, step in others.items():
            got = harness.run_reference(step, *args)
            yield {"seed": seed, "variant": v, **compare.numbers(got, ref)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()
    import jax
    jax.config.update("jax_compilation_cache_dir", str(BENCH / ".jax_cache"))
    from bench import harness
    if not args.cpu:
        try:
            harness.check_device(1)
        except harness.NoChip as e:
            print(f"bench/control.py: {e}", file=sys.stderr)
            return 3
    t0 = time.time()
    for r in readings(ROOT, args.workload, args.seeds):
        print(json.dumps(r), flush=True)
    print(f"control readings took {time.time() - t0:.1f} s", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
