"""Chip benchmark harness: cells named in BENCHMARK.json, driven by data."""
