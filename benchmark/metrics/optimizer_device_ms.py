"""Own device time of the operations under the ``optimizer`` scope
(``tx.update`` and ``apply_updates``), per traced step."""

from benchmark import host_regions


def read(run):
    return host_regions.scope_ms(run, ("optimizer",),
                                 run["trace"].get("steps"))
