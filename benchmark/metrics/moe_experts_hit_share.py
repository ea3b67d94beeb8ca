"""Distinct experts a decode step touched, per layer, as a share of the
layer's experts: ``experts_hit`` of the traced window's
``rt:engine.decode.moe`` regions over steps x layers x experts.  The share
of the expert weights a step has to read."""

from benchmark import moe_scopes


def read(run):
    routing = moe_scopes.decode_routing(run)
    if not routing:
        return None
    config = run["cell"]["config"]
    return 100.0 * routing["experts_hit"] / (
        routing["steps"] * config["num_hidden_layers"]
        * config["num_experts"])
