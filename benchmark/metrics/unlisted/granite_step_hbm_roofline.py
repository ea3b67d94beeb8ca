"""The WHOLE decode step's share of its roofline for a model of Mamba-2 and
grouped-query layers with experts in every layer, of which the program holds
a share: the least time the chip could take for what a step must move (the
weights it reads whatever was routed and the routed experts that were HIT,
the held positions' keys and values in the attention layers, the live slots'
states read and written in the Mamba layers: ``costs_kda.step``, whose terms
are these) and compute, over the step's device time.  Counted from what the
engine's regions say of the traced window's steps
(``rt:engine.decode.dispatch``: ``active``, ``live_tokens``;
``rt:engine.decode.moe``: ``experts_hit``), so it cannot pass 100%."""

from benchmark import costs, costs_kda, host_regions, moe_scopes, spec


def read(run):
    program = (run["trace"] or {}).get("programs", {}).get(
        host_regions.DECODE)
    steps = host_regions.rows(run, "engine.decode.dispatch")
    routing = moe_scopes.decode_routing(run)
    if not program or not steps or not routing:
        return None
    config = run["cell"]["config"]
    family = spec.load_part("families", config["family"])
    n = len(steps)
    least = costs.least_seconds(costs_kda.step(
        sum(s["active"] for s in steps) / n,
        family.decode_weight_params(
            config, routing["experts_hit"] / routing["steps"]),
        sum(s["live_tokens"] for s in steps) / n,
        family.kv_bytes_per_token(config),
        family.state_bytes_per_slot(config)), run["peaks"])
    return 100.0 * least / (program["device_s"] / program["calls"])
