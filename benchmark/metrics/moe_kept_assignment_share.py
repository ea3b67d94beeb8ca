"""Of the assignments the traced window's decode steps MADE (every live
token's experts a layer, over all the router's experts), the share that fell
on an expert the program HOLDS and was computed here: ``assignments`` over
``assignments_made`` of the ``rt:engine.decode.moe`` regions.  A chip that
holds one of n equal shares reads 100 / n under even routing; a program that
holds every expert reads 100.  Regions without ``assignments_made`` (the
parent of the PR that added it) give nothing to read."""

from benchmark import host_regions


def read(run):
    steps = [s for s in host_regions.rows(run, "engine.decode.moe") or ()
             if s.get("assignments_made")]
    if not steps:
        return None
    return 100.0 * sum(s["assignments"] for s in steps) \
        / sum(s["assignments_made"] for s in steps)
