"""Of the positions the live sequences held, the share their queries' reads
kept, summed over the traced window's decode steps: ``selected`` over
``live`` of ``rt:engine.decode.dispatch`` (a sequence keeps ``min(index_topk,
pos + 1)``).  None for a program that selects nothing."""

from benchmark import host_regions


def read(run):
    steps = [s for s in host_regions.rows(run, "engine.decode.dispatch")
             or [] if "selected" in s]
    if not steps:
        return None
    return 100.0 * sum(s["selected"] for s in steps) \
        / sum(s["live"] for s in steps)
