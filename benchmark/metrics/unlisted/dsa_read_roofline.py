"""The sparse attention's share of its roofline in a decode step: the least
time the chip could take to score the live sequences' indexer keys and read
the latent rows their queries kept, once a layer, with the indexer's matrices
(``costs_dsa.selection`` over the ``live`` and ``selected`` positions of the
traced window's ``rt:engine.decode.dispatch``, a mean step's), over the own
device time of everything under ``dsa_index``, ``dsa_select`` and
``dsa_read`` in a ``jit__decode`` call.  The selection's own time counts
against it and adds nothing to the least, so it cannot pass 100."""

from benchmark import costs, costs_dsa, decode_scopes, host_regions, spec


def read(run):
    steps = [s for s in host_regions.rows(run, "engine.decode.dispatch")
             or [] if "selected" in s]
    per_step_ms = decode_scopes.decode_scope_ms(
        run, ("dsa_index", "dsa_select", "dsa_read"))
    if not steps or not per_step_ms:
        return None
    config = run["cell"]["config"]
    family = spec.load_part("families", config["family"])
    least = costs.least_seconds(costs_dsa.selection(
        sum(s["live"] for s in steps) / len(steps),
        sum(s["selected"] for s in steps) / len(steps),
        index_params=family.layer_params(config)["indexer"],
        **family.dsa_shape(config)), run["peaks"])
    return 100.0 * least / (per_step_ms * 1e-3)
