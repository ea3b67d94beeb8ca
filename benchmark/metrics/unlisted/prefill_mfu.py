"""The WHOLE prefill's share of the chip's bf16 peak: the model operations
of a mean traced prefill (``costs_prefill.model_operations``: the matrices
by the REAL positions of ``rt:engine.prefill``'s ``prompt_len``, not the
rung's; the experts by the assignments of ``rt:engine.prefill.moe``;
attention's causal half in the layers that have it; the head on one
position) over the device time of a ``jit__prefill`` call.  Counted from the
regions, so that padding, the dispatch's copies and every operation beside
the products lower it and nothing can raise it past 100."""

from benchmark import costs_prefill, prefill_scopes, spec


def read(run):
    found = prefill_scopes.prefill_regions(run)
    device_ms = prefill_scopes.prefill_device_ms(run)
    if not found or not device_ms:
        return None
    config = run["cell"]["config"]
    shape = spec.load_part("families", config["family"]).prefill_shape(config)
    lengths = [p["prompt_len"] for p in found["prefills"]]
    routing = found["routing"]
    operations = costs_prefill.model_operations(
        sum(lengths) / len(lengths),
        sum(r["assignments"] for r in routing) / len(routing)
        if routing else 0.0,
        squared_positions=sum(n * n for n in lengths) / len(lengths),
        **shape)
    return 100.0 * operations / (
        run["peaks"]["bf16_flops_per_s"] * device_ms * 1e-3)
