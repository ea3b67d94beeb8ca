"""The decode step's share of its memory roofline: the bytes a step must
read (weights in the compute type, and the live contexts' keys and
values) over the published bandwidth, over the step's device time."""

from benchmark import costs, spec, stats


def read(run):
    program = run["trace"].get("programs", {}).get("jit__decode")
    live = stats.live_kv_tokens_per_step(run["requests"],
                                         run["replica"]["decode_steps"])
    if not program or live is None:
        return None
    config = run["cell"]["config"]
    family = spec.load_part("families", config["family"])
    least = costs.decode_step_bytes(
        family.decode_weight_params(config),
        family.kv_bytes_per_token(config), live) \
        / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (program["device_s"] / program["calls"])
