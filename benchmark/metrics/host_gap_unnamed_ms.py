"""Device-idle time between programs, per ``jit__decode`` call, that the
host spent in no region of the engine: what the other kinds leave of the
idle time between programs."""

from benchmark import host_regions


def read(run):
    return host_regions.gap_ms(run, "unnamed")
