"""Device-idle time between programs, per ``jit__decode`` call, between the
exec thread's return and the engine's loop running again on the actor's
event loop: ``resume_us`` before each ``rt:engine.deliver``."""

from benchmark import host_regions


def read(run):
    return host_regions.gap_ms(run, "resume")
