"""The WHOLE decode step's share of its roofline for a model of latent
attention behind an indexer with expert layers of which the program holds a
share: the least time the chip could take for what a step must move (the
weights it reads whatever was routed and the routed experts that were HIT,
the live positions' indexer keys, the latent rows the queries kept:
``costs_dsa.step``) and compute, over the step's device time.  Counted from
what the engine's regions say of the traced window's steps
(``rt:engine.decode.dispatch``: ``active``, ``live``, ``selected``;
``rt:engine.decode.moe``: ``experts_hit``), so it cannot pass 100%."""

from benchmark import costs, costs_dsa, host_regions, moe_scopes, spec


def read(run):
    program = (run["trace"] or {}).get("programs", {}).get(
        host_regions.DECODE)
    steps = [s for s in host_regions.rows(run, "engine.decode.dispatch")
             or [] if "selected" in s]
    routing = moe_scopes.decode_routing(run)
    if not program or not steps or not routing:
        return None
    config = run["cell"]["config"]
    family = spec.load_part("families", config["family"])
    n = len(steps)
    least = costs.least_seconds(costs_dsa.step(
        sum(s["active"] for s in steps) / n,
        family.decode_weight_params(
            config, routing["experts_hit"] / routing["steps"]),
        sum(s["live"] for s in steps) / n,
        sum(s["selected"] for s in steps) / n,
        family.dsa_shape(config)), run["peaks"])
    return 100.0 * least / (program["device_s"] / program["calls"])
