"""How uneven the routing of the decode steps was: the largest
single-expert load over the mean load (assignments / experts), both summed
over the layers and the traced window's steps (``load_max`` and
``assignments`` of ``rt:engine.decode.moe``).  1 is perfectly even."""

from benchmark import moe_scopes


def read(run):
    routing = moe_scopes.decode_routing(run)
    if not routing or not routing["assignments"]:
        return None
    return routing["load_max"] * run["cell"]["config"]["num_experts"] \
        / routing["assignments"]
