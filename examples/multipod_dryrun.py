"""Multi-pod dry-run example: lower + compile one cell on the 512-chip mesh
and print its memory/cost/collective analysis.

    PYTHONPATH=src python examples/multipod_dryrun.py --arch llama3.2-1b \
        --shape train_4k
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse  # noqa: E402

from repro.compile_cache import use_compile_cache  # noqa: E402


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--single-pod", action="store_true")
    args = ap.parse_args()
    from repro.launch.dryrun import run_cell
    res = run_cell(args.arch, args.shape, multi_pod=not args.single_pod)
    print("\nresult:", {k: v for k, v in res.items() if k != "trace"})


if __name__ == "__main__":
    main()
