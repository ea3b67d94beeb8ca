"""``moe_experts_roofline`` for a configuration that names its expert
layers' shape under other keys (``families/<family>.py::moe_shape``: the
layers that route, the routed experts, an expert's two widths): the least
time for the traced steps' assignments and the experts those steps TOUCHED,
at the bytes a parameter the program stores them in, over the own device
time of everything under ``moe_experts`` in the traced ``jit__decode``
calls.  A shared expert is not counted: its time lies under ``moe_shared``."""

from benchmark import costs, costs_moe, moe_scopes, spec


def read(run):
    routing = moe_scopes.decode_routing(run)
    per_call_ms = moe_scopes.decode_scope_ms(run, ("moe_experts",))
    if not routing or not per_call_ms:
        return None
    config = run["cell"]["config"]
    shape = spec.load_part("families", config["family"]).moe_shape(config)
    least = costs.least_seconds(costs_moe.grouped_matmuls(
        routing["assignments"], routing["experts_hit"], shape["hidden"],
        shape["width"], routing["weight_itemsize"]), run["peaks"])
    return 100.0 * (least / routing["steps"]) / (per_call_ms * 1e-3)
