"""The on-chip benchmark of this repository: see harness.py and
BENCHMARK.json at the repository root."""
