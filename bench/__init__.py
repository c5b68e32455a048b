"""Chip benchmark of the PREBA serving path (see BENCHMARK.json and PERF.md)."""
