"""Device-idle time between programs, per ``jit__decode`` call, between the
loop's ``run_in_executor`` and the exec thread entering the step:
``submit_us`` before each ``rt:engine.decode.dispatch`` and
``rt:engine.prefill``."""

from benchmark import host_regions


def read(run):
    return host_regions.gap_ms(run, "submit")
