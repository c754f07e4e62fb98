"""The seeded chaos harness (``bench_chaos``); the benchmark is ``bench/run.py``."""
