"""The on-chip benchmark: one command that runs one cell of BENCHMARK.json.

See ``run.py`` for the command line and PERF.md for the cells and metrics.
"""
