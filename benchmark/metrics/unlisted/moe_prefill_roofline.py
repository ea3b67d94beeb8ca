"""The grouped matmuls' share of their roofline IN THE PREFILL: for each
traced prefill the least time the chip could take for its assignments and
the experts it touched (``costs_moe.grouped_matmuls`` over the
``rt:engine.prefill.moe`` region: the larger of operations over the bf16 peak
and the touched experts' bytes over the HBM's rate), a mean prefill's, over
the own device time of everything under ``moe_experts`` in a ``jit__prefill``
call.  At 128-256 rows an expert the two sides of the roofline meet (the
v5e's ridge is 240 rows), so either may be the larger."""

from benchmark import costs, costs_moe, prefill_scopes, spec


def read(run):
    found = prefill_scopes.prefill_regions(run)
    per_call_ms = prefill_scopes.prefill_scope_ms(run, ("moe_experts",))
    if not found or not found["routing"] or not per_call_ms:
        return None
    config = run["cell"]["config"]
    shape = spec.load_part("families", config["family"]).moe_shape(config)
    least = [costs.least_seconds(costs_moe.grouped_matmuls(
        r["assignments"], r["experts_hit"], shape["hidden"], shape["width"],
        r["weight_itemsize"]), run["peaks"]) for r in found["routing"]]
    return 100.0 * (sum(least) / len(least)) / (per_call_ms * 1e-3)
