"""The benchmark's harness: what every cell shares.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own, found by the name that
``BENCHMARK.json`` gives it (see ``cell.py``).
"""
