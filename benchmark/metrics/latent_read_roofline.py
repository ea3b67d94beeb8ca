"""The decode steps' read of the latent pages as a share of its roofline:
the least time the chip could take for the rows the live sequences HELD
(``live_tokens`` of the traced window's ``rt:engine.decode.dispatch``
regions, in every layer, ``costs_mla.latent_read``) over the own device time
of everything under ``latent_read`` in the traced ``jit__decode`` calls.
The gather takes every reserved page whatever the live length, so pages
gathered and not held show here as a low share."""

from benchmark import costs, costs_mla, decode_scopes, host_regions


def read(run):
    steps = [s for s in host_regions.rows(run, "engine.decode.dispatch")
             or () if "live_tokens" in s]
    per_call_ms = decode_scopes.decode_scope_ms(run, ("latent_read",))
    if not steps or not per_call_ms:
        return None
    config = run["cell"]["config"]
    least = costs.least_seconds(costs_mla.latent_read(
        sum(s["live_tokens"] for s in steps), config["num_hidden_layers"],
        config["kv_lora_rank"], config["qk_rope_head_dim"],
        config["num_attention_heads"]), run["peaks"])
    return 100.0 * (least / len(steps)) / (per_call_ms * 1e-3)
