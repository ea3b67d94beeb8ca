"""Device time of the decode program per step."""


def read(run):
    program = run["trace"].get("programs", {}).get("jit__decode")
    return 1e3 * program["device_s"] / program["calls"] if program else None
