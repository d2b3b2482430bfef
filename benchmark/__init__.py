"""The repo's benchmark: a grid of cells (one configuration under one traffic
mix each), found by name from ``BENCHMARK.json``.  See ``benchmark/README.md``.
"""
