"""Benchmark of LMC training on a TPU: harness, reference and yardstick.

Run a cell with ``python3 bench/run.py``; ``BENCHMARK.json`` at the root of
the repository lists the cells and their metrics.
"""
