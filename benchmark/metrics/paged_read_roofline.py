"""The token steps' paged read as a share of its roofline: the least time
the chip could take for the keys and values of the positions the stepped
sequences HELD (``live_tokens`` of the traced window's
``rt:engine.decode.dispatch`` regions that step one token a sequence, every
pool layer's, ``costs_block.block_read``) over the own device time of
everything under ``paged_read`` in the traced ``jit__decode`` calls: the
token step's twin of ``block_read_roofline``.  It counts the live bytes, so
what a read takes beside them (a gather's pages of the rung that nobody
holds, a kernel's block rounded up) shows as a low share and nothing can
pass 100%.  A cell whose steps are block steps, or a program without the
scope, gives nothing to read."""

from benchmark import costs, costs_block, decode_scopes, host_regions, spec


def read(run):
    steps = [s for s in host_regions.rows(run, "engine.decode.dispatch")
             or () if "live_tokens" in s and "block_len" not in s]
    per_call_ms = decode_scopes.decode_scope_ms(run, ("paged_read",))
    if not steps or not per_call_ms:
        return None
    config = run["cell"]["config"]
    family = spec.load_part("families", config["family"])
    least = costs.least_seconds(costs_block.block_read(
        sum(s["live_tokens"] for s in steps),
        family.kv_bytes_per_token(config)), run["peaks"])
    return 100.0 * (least / len(steps)) / (per_call_ms * 1e-3)
