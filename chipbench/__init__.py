"""Chip benchmark of the JAX migration path: see BENCHMARK.json and PERF.md."""
