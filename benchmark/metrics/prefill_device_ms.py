"""Device time of the prefill program per call."""


def read(run):
    program = run["trace"].get("programs", {}).get("jit__prefill")
    return 1e3 * program["device_s"] / program["calls"] if program else None
