"""Worst LoopWatchdog reading of the node's event loop in the window."""


def read(run):
    return max(run["loop_lag_ms"]) if run["loop_lag_ms"] else None
