"""Device-idle time between programs, per ``jit__decode`` call, that the
host spent in ``rt:engine.deliver``: pushing each slot's token to its
stream and retiring what finished."""

from benchmark import host_regions


def read(run):
    return host_regions.gap_ms(run, "deliver")
