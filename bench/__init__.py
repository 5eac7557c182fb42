"""The benchmark: one harness driven by data (``BENCHMARK.json`` and the
files under this directory). Entry point: ``python3 bench/run.py``."""
