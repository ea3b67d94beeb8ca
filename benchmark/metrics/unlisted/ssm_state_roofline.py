"""The state-space rule's token step as a share of its roofline: the least
time the chip could take to read and write the float32 states of the slots
that were LIVE (``active`` of the traced window's
``rt:engine.decode.dispatch`` regions), every Mamba layer's
(``costs_ssm.state_step``, whose bytes are ``costs_linear.state_step``'s),
over the own device time of everything under ``linear_state`` in the traced
``jit__decode`` calls.  A step that reads the states twice, spreads the one
key and query over the heads in HBM, or touches parked slots' rows, shows
here as a low share."""

from benchmark import costs, costs_ssm, decode_scopes, host_regions, spec


def read(run):
    steps = host_regions.rows(run, "engine.decode.dispatch")
    per_call_ms = decode_scopes.decode_scope_ms(run, ("linear_state",))
    if not steps or not per_call_ms:
        return None
    config = run["cell"]["config"]
    shape = spec.load_part("families", config["family"]).linear_shape(config)
    least = costs.least_seconds(costs_ssm.state_step(
        sum(s["active"] for s in steps) / len(steps), shape["layers"],
        shape["heads"], shape["key_dim"], shape["value_dim"]), run["peaks"])
    return 100.0 * least / (per_call_ms * 1e-3)
