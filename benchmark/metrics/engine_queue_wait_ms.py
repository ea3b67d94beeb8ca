"""Median time a sequence waited between ``generate()`` and its prefill:
the ``waited_us`` of the traced window's ``rt:engine.prefill``."""

from benchmark import host_regions


def read(run):
    return host_regions.median_ms(run, "engine.prefill", "waited_us")
