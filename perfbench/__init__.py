"""Chip benchmark of the serve plane: data-driven cells, one harness.

``BENCHMARK.json`` at the repository root names the cells; each cell's
configuration, traffic mix and per-layer metric reader sit in files of
their own under this directory, found by name.
"""
