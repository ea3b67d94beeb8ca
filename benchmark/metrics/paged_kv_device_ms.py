"""Own device time of the operations under the ``paged_append`` and
``paged_read`` scopes (the scatter into the page pools and the gather out
of them), per ``jit__decode`` call."""

from benchmark import host_regions


def read(run):
    decode = run["trace"].get("programs", {}).get("jit__decode")
    return host_regions.scope_ms(run, ("paged_append", "paged_read"),
                                 decode and decode["calls"])
