"""Chip benchmark of the Ditto serving path (see ``BENCHMARK.json`` at the repository root)."""
