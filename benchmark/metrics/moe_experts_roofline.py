"""The decode steps' grouped matmuls as a share of their roofline: the least
time the chip could take for the traced steps' assignments and the experts
those steps touched (``costs_moe.grouped_matmuls``; from the engine's
``rt:engine.decode.moe`` regions, never from all the experts, at the bytes a
parameter the program stores them in), over the own device time of
everything under ``moe_experts`` in the traced ``jit__decode`` calls."""

from benchmark import costs, costs_moe, moe_scopes


def read(run):
    routing = moe_scopes.decode_routing(run)
    per_call_ms = moe_scopes.decode_scope_ms(run, ("moe_experts",))
    if not routing or not per_call_ms:
        return None
    config = run["cell"]["config"]
    least = costs.least_seconds(costs_moe.grouped_matmuls(
        routing["assignments"], routing["experts_hit"],
        config["hidden_size"], config["intermediate_size"],
        routing["weight_itemsize"]), run["peaks"])
    return 100.0 * (least / routing["steps"]) / (per_call_ms * 1e-3)
