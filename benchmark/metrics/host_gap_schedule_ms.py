"""Device-idle time between programs, per ``jit__decode`` call, that the
host spent in ``rt:engine.schedule``: the cancel and deadline sweeps,
admission, and building the step's token, position and table arrays."""

from benchmark import host_regions


def read(run):
    return host_regions.gap_ms(run, "schedule")
