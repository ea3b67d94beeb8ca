"""How uneven the routing of the traced prefills was: the largest
single-expert load (summed over layers and prefills) over the mean load an
expert (assignments over experts, over the same).  1 is even; the grouped
matmuls' tiles are sized for the mean."""

from benchmark import prefill_scopes, spec


def read(run):
    found = prefill_scopes.prefill_regions(run)
    routing = found and found["routing"]
    assignments = sum(r["assignments"] for r in routing or ())
    if not assignments:
        return None
    config = run["cell"]["config"]
    shape = spec.load_part("families", config["family"]).moe_shape(config)
    return sum(r["load_max"] for r in routing) * shape["experts"] \
        / assignments
