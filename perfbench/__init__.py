"""The benchmark: see ``BENCHMARK.json`` and ``perfbench/run.py``."""
