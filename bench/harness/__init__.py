"""The benchmark harness: cell lookup, traffic, drivers, trace reduction.

Everything here is found by name from ``BENCHMARK.json`` and the data
files under ``bench/``; a new configuration, traffic mix, cell or
per-layer metric is a new file, never an edit of this package.
"""
