"""Chip benchmark of the serving path (see BENCHMARK.json and PERF.md)."""
