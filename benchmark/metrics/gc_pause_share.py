"""Share of the traced window that the replica spent inside collector
passes: the union of its ``rt:gc`` regions (``tracing.watch_gc``) over
``window_s``.  The collector holds the GIL: both threads stall."""

from benchmark import host_threads


def read(run):
    return host_threads.gc_pause_share(run)
