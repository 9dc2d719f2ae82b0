"""Benchmark harness: see run.py and harness.py."""
