#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of the machine it starts on.

    python3 bench/run.py --workload gcnii-flickr.segment --seed 7 --seconds 10 \\
        --trace 0

Prints, as the last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device``, with
``--trace 1`` also ``breakdown``, and last ``checks``: each number the
comparison with the reference read, beside its limit. The same numbers are
the last lines of standard error. Off a TPU, or on fewer chips than the cell
asks for, it exits with code 3 and prints no result.

JAX's persistent compilation cache lives in ``bench/.jax_cache`` of the
checkout, so only a checkout's first run of a cell compiles.
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    import jax
    jax.config.update("jax_compilation_cache_dir", str(BENCH / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    from bench import harness
    try:
        result, checks = harness.run_cell(
            ROOT, args.workload, args.seed, args.seconds, bool(args.trace),
            t_start=T_START)
    except harness.NoChip as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 3
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
