"""Device busy time per train step of the traced steps."""


def read(run):
    trace = run["trace"]
    return 1e3 * trace["busy_s"] / trace["steps"] if trace else None
