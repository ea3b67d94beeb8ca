"""Collective operations' own time, that in which nothing else runs inside
their span on that device, over the traced window; mean over the devices."""


def read(run):
    trace = run["trace"]
    if not trace or trace["devices"] < 2:
        return None
    return 100.0 * trace["collective_exposed_s"] / trace["window_s"]
