"""The WHOLE decode step's share of its roofline for a model with linear and
full layers: the least time the chip could take for what a step must move
(every weight once, the held positions' keys and values in the full layers,
the live slots' states read and written: ``costs_linear.hybrid_step``) and
compute, over the step's device time.  Counted from what the engine's
regions say of the traced window's steps (``rt:engine.decode.dispatch``:
``active``, ``live_tokens``), so it cannot pass 100%."""

from benchmark import costs, costs_linear, host_regions, spec


def read(run):
    program = (run["trace"] or {}).get("programs", {}).get(
        host_regions.DECODE)
    steps = host_regions.rows(run, "engine.decode.dispatch")
    if not program or not steps:
        return None
    config = run["cell"]["config"]
    family = spec.load_part("families", config["family"])
    n = len(steps)
    least = costs.least_seconds(costs_linear.hybrid_step(
        sum(s["active"] for s in steps) / n,
        family.decode_weight_params(config),
        sum(s["live_tokens"] for s in steps) / n,
        family.kv_bytes_per_token(config),
        family.state_bytes_per_slot(config)), run["peaks"])
    return 100.0 * least / (program["device_s"] / program["calls"])
