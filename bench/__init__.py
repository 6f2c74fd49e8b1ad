"""The chip benchmark: cells of ``BENCHMARK.json``, run by ``bench/run.py``."""
