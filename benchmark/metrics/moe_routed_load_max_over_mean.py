"""``moe_load_max_over_mean`` by the family's ``moe_shape``: the largest
single-expert load over the mean load (assignments / routed experts), both
summed over the routing layers and the traced window's steps."""

from benchmark import moe_scopes, spec


def read(run):
    routing = moe_scopes.decode_routing(run)
    if not routing or not routing["assignments"]:
        return None
    config = run["cell"]["config"]
    shape = spec.load_part("families", config["family"]).moe_shape(config)
    return routing["load_max"] * shape["experts"] / routing["assignments"]
