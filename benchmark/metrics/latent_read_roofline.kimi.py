"""``latent_read_roofline`` for a model only SOME of whose layers keep latent
pages (the family's ``latent_shape``: the latent layers, not
``num_hidden_layers``): the least time for the rows the live sequences HELD
(``live_tokens`` of the traced window's ``rt:engine.decode.dispatch``
regions) in every latent layer (``costs_mla.latent_read``) over the own
device time of everything under ``latent_read`` in the traced ``jit__decode``
calls."""

from benchmark import costs, costs_mla, decode_scopes, host_regions, spec


def read(run):
    steps = [s for s in host_regions.rows(run, "engine.decode.dispatch")
             or () if "live_tokens" in s]
    per_call_ms = decode_scopes.decode_scope_ms(run, ("latent_read",))
    if not steps or not per_call_ms:
        return None
    config = run["cell"]["config"]
    shape = spec.load_part("families", config["family"]).latent_shape(config)
    least = costs.least_seconds(costs_mla.latent_read(
        sum(s["live_tokens"] for s in steps), shape["layers"], shape["rank"],
        shape["rope"], shape["heads"]), run["peaks"])
    return 100.0 * (least / len(steps)) / (per_call_ms * 1e-3)
