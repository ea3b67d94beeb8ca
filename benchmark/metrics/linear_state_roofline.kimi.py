"""``linear_state_roofline`` for the rule with a decay a key channel: the
least time the chip could take to read and write the float32 states of the
slots that were LIVE (``active`` of the traced window's
``rt:engine.decode.dispatch`` regions), every KDA layer's
(``costs_kda.state_step``), over the own device time of everything under
``linear_state`` in the traced ``jit__decode`` calls."""

from benchmark import costs, costs_kda, decode_scopes, host_regions, spec


def read(run):
    steps = host_regions.rows(run, "engine.decode.dispatch")
    per_call_ms = decode_scopes.decode_scope_ms(run, ("linear_state",))
    if not steps or not per_call_ms:
        return None
    config = run["cell"]["config"]
    shape = spec.load_part("families", config["family"]).linear_shape(config)
    least = costs.least_seconds(costs_kda.state_step(
        sum(s["active"] for s in steps) / len(steps), shape["layers"],
        shape["heads"], shape["key_dim"], shape["value_dim"]), run["peaks"])
    return 100.0 * least / (per_call_ms * 1e-3)
