"""Cached positions the live sequences held over the positions the paged
read gathered, summed over the traced window's decode steps:
``live_tokens`` and ``gathered_tokens`` of ``rt:engine.decode.dispatch``.
The decode twin of ``prefill_useful_share``: the gather takes every page a
slot may use, whatever the live length."""

from benchmark import host_regions


def read(run):
    steps = [s for s in host_regions.rows(run, "engine.decode.dispatch")
             or () if "gathered_tokens" in s]
    if not steps:
        return None
    return 100.0 * sum(s["live_tokens"] for s in steps) \
        / sum(s["gathered_tokens"] for s in steps)
