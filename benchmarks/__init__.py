"""Benchmark harness package (python -m benchmarks.run_all)."""
