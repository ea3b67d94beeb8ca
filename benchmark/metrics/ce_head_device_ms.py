"""Own device time of the operations under the ``ce_head`` scope (head
product and cross-entropy, forward and backward), per traced step."""

from benchmark import host_regions


def read(run):
    return host_regions.scope_ms(run, ("ce_head",),
                                 run["trace"].get("steps"))
