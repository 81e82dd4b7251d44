"""Chip benchmark: see harness.py and BENCHMARK.json."""
