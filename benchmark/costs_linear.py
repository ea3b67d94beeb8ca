"""Operations and bytes of linear-attention layers (the gated delta rule,
``ray_tpu/ops/linear_attention.py``) and of a decode step of a model that
has them beside full-attention layers, computed from shapes and from what
the engine's regions say the traced steps and prefills held.

As in ``costs_block``: the states are those of the slots that were LIVE and
the cached positions those the stepped sequences HELD, the scan is counted
for a prompt's REAL length (whole chunks of it), never the padded rung's: a
program that moves or computes more than it must reads honestly low, and
nothing here can pass 100%.
"""

from __future__ import annotations

from benchmark import costs

CHUNK = 64


def state_step(live_slots: float, layers: int, heads: int, key_dim: int,
               value_dim: int) -> dict:
    """One position of the rule for ``live_slots`` slots in ``layers``
    layers: every float32 state [heads, key_dim, value_dim] read once and
    written once; per value of it the two sums (S^T k, S^T q: a multiply
    and an add each) and the update (decay, outer product, add)."""
    values = live_slots * layers * heads * key_dim * value_dim
    return {"flops": 7.0 * values, "bytes": 2.0 * values * costs.F32}


def chunked_scan(length: int, layers: int, heads: int, key_dim: int,
                 value_dim: int, chunk: int = CHUNK) -> dict:
    """The chunked rule over a sequence of ``length`` real positions in
    ``layers`` layers, from an empty state.  A chunk of c positions a head:
    five c x c products (k beta k^T, q k^T and T k beta alpha over key_dim; T
    v beta and the inner attention over value_dim), the unit triangular
    solve (c^3), and three products with the state (2 c key_dim value_dim
    each).  Bytes: q, k, v and the two gates read and o written once, float32,
    and the state written once."""
    chunks = -(-length // chunk)
    per_chunk = 2.0 * chunk * chunk * (3 * key_dim + 2 * value_dim) \
        + chunk ** 3 + 6.0 * chunk * key_dim * value_dim
    rows = length * heads * (2 * key_dim + 2 * value_dim + 2)
    return {"flops": layers * heads * chunks * per_chunk,
            "bytes": layers * costs.F32 * (
                rows + heads * key_dim * value_dim)}


def hybrid_step(live_slots: float, weight_params: int, live_tokens: float,
                kv_bytes_per_token: int, state_bytes_per_slot: int) -> dict:
    """One decode step of a model with both kinds of layer: every weight
    once (bf16; ``live_slots`` products each), the held positions' keys and
    values in the full layers, and the live slots' states read and written.
    Attention's own products (one row against the cache) and the rule's are
    not counted.  Means over steps may be passed: every term is linear."""
    return {"flops": 2.0 * live_slots * weight_params,
            "bytes": weight_params * costs.BF16
            + live_tokens * kv_bytes_per_token
            + 2.0 * live_slots * state_bytes_per_slot}
