"""Chip benchmark of FLAME serving: one cell of BENCHMARK.json per run."""
