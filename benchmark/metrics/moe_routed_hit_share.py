"""``moe_experts_hit_share`` by the family's ``moe_shape``: distinct routed
experts a decode step touched, per routing layer, as a share of the layer's
routed experts."""

from benchmark import moe_scopes, spec


def read(run):
    routing = moe_scopes.decode_routing(run)
    if not routing:
        return None
    config = run["cell"]["config"]
    shape = spec.load_part("families", config["family"]).moe_shape(config)
    return 100.0 * routing["experts_hit"] / (
        routing["steps"] * shape["layers"] * shape["experts"])
