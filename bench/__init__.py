"""The benchmark of this repository: see run.py and BENCHMARK.json."""
