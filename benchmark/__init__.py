"""The on-chip benchmark: BENCHMARK.json at the root names what is here.

A regular package, so that the workers of the cluster import the copy
that ``run.py`` put first on PYTHONPATH and no other.
"""
