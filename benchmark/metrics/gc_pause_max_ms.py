"""The longest collector pass of the traced seconds: the longest ``rt:gc``
region (a full pass is tens of milliseconds, and a token waits for it)."""

from benchmark import host_threads


def read(run):
    return host_threads.gc_pause_max_ms(run)
