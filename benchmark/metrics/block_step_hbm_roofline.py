"""The WHOLE block step's share of its roofline: the least time the chip
could take for what a step must move (the attention, router and head
matrices; the experts the step TOUCHED, in the type they are stored in; the
keys and values of the positions the stepped sequences held) and compute
(``costs_block.block_step``), over the step's device time.  Counted from
what the engine's regions say of the traced window's steps
(``rt:engine.decode.dispatch``: ``active``, ``block_len``, ``live_tokens``;
``rt:engine.decode.moe``: ``assignments``, ``experts_hit``,
``weight_itemsize``), so it cannot pass 100%."""

from benchmark import costs, costs_block, host_regions, moe_scopes, spec


def read(run):
    program = (run["trace"] or {}).get("programs", {}).get(
        host_regions.DECODE)
    steps = [s for s in host_regions.rows(run, "engine.decode.dispatch")
             or () if "block_len" in s]
    routing = moe_scopes.decode_routing(run)
    if not program or not steps or not routing:
        return None
    config = run["cell"]["config"]
    family = spec.load_part("families", config["family"])
    shape = family.moe_shape(config)
    n, routed = len(steps), routing["steps"]
    # a step's means: the two kinds of region may count a step apart at the
    # traced window's edges
    least = costs.least_seconds(costs_block.block_step(
        sum(s["active"] * s["block_len"] for s in steps) / n,
        family.step_weight_params(config),
        routing["assignments"] / routed, routing["experts_hit"] / routed,
        shape["hidden"], shape["width"], routing["weight_itemsize"],
        sum(s["live_tokens"] for s in steps) / n,
        family.kv_bytes_per_token(config)), run["peaks"])
    return 100.0 * least / (program["device_s"] / program["calls"])
