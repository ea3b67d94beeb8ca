"""Device-idle time between programs, per ``jit__decode`` call, that the
host spent waiting in ``rt:engine.decode.fetch`` after the device had
finished the step: the copy back of its tokens and the exec thread's
wake-up."""

from benchmark import host_regions


def read(run):
    return host_regions.gap_ms(run, "fetch")
