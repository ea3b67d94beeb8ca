"""What a live stream costs the actor's loop thread a decode step, in
microseconds: the window's ``step_loop_cpu_us`` over its ``active`` slots
(both of ``rt:engine.decode.dispatch``).  Comparable across cells, whose
steps hold 8 to 64 live slots."""

from benchmark import loop_split


def read(run):
    return loop_split.ratio(run, ("step_loop_cpu_us",), ("active",))
