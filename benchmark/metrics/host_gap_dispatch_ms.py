"""Device-idle time between programs, per ``jit__decode`` call, that the
host spent in ``rt:engine.decode.dispatch`` or ``rt:engine.prefill``: the
jitted call's enqueue and the transfer of its host arrays."""

from benchmark import host_regions


def read(run):
    return host_regions.gap_ms(run, "dispatch")
