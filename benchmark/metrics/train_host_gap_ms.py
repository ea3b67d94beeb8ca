"""Mean device-idle gap between consecutive programs of the traced train
steps: what the host's loop leaves the device waiting for."""


def read(run):
    trace = run["trace"]
    if not trace or trace["steps"] < 2:
        return None
    return 1e3 * trace["program_gap_s"] / (trace["steps"] - 1)
