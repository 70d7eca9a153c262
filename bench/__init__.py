"""Chip benchmark of the DSE service (see BENCHMARK.json and PERF.md)."""
