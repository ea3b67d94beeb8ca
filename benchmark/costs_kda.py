"""Operations and bytes of Kimi Delta Attention's rule (the delta rule with a
decay a KEY CHANNEL, ``ray_tpu/ops/linear_attention.py``'s ``kda_*``) and of
a decode step of a model that has it beside latent-attention layers and
expert layers of which the program holds a share, computed from shapes and
from what the engine's regions say the traced steps held.

As in ``costs_linear``: the states are those of the slots that were LIVE, the
cached positions those the stepped sequences HELD, the routed experts those
a step TOUCHED of the experts held here, the scan is counted for a prompt's
REAL length (whole chunks of it), never the padded rung's: a program that
moves or computes more than it must reads honestly low, and nothing here can
pass 100%.
"""

from __future__ import annotations

from benchmark import costs

CHUNK, SUB_CHUNK = 64, 16


def state_step(live_slots: float, layers: int, heads: int, key_dim: int,
               value_dim: int) -> dict:
    """One position of the rule for ``live_slots`` slots in ``layers``
    layers: every float32 state [heads, key_dim, value_dim] read once and
    written once; per value of it the row's decay (one multiply), the two
    sums (S^T k, S^T q: a multiply and an add each) and the update (outer
    product, add): 8, one more than a decay a head costs, which scales the
    sums and not the state."""
    values = live_slots * layers * heads * key_dim * value_dim
    return {"flops": 8.0 * values, "bytes": 2.0 * values * costs.F32}


def chunked_scan(length: int, layers: int, heads: int, key_dim: int,
                 value_dim: int, chunk: int = CHUNK,
                 sub: int = SUB_CHUNK) -> dict:
    """The chunked rule over a sequence of ``length`` real positions in
    ``layers`` layers, from an empty state.  A chunk of c positions a head:
    the two decayed c x c matrices (k against k, q against k), each the
    products of a sub-chunk's rows with the positions before it (2 c c
    key_dim over the lower blocks: (n - 1) / 2n of the square, n = c / sub)
    and the pair-by-pair part inside the sub-chunks (3 c sub key_dim: a
    multiply by the decay, by the other row, an add); T k beta exp(G) over
    key_dim and T v beta and the inner attention over value_dim (2 c c
    each); the unit triangular solve (c^3); three products with the state (2
    c key_dim value_dim each).  Bytes: q, k, v and the decay a key channel
    read and o written once, float32, beta, and the state written once."""
    chunks = -(-length // chunk)
    n = chunk // sub
    decayed = 2.0 * chunk * chunk * key_dim * (n - 1) / (2 * n) \
        + 3.0 * chunk * sub * key_dim
    per_chunk = 2 * decayed \
        + 2.0 * chunk * chunk * (key_dim + 2 * value_dim) \
        + chunk ** 3 + 6.0 * chunk * key_dim * value_dim
    rows = length * heads * (3 * key_dim + 2 * value_dim + 1)
    return {"flops": layers * heads * chunks * per_chunk,
            "bytes": layers * costs.F32 * (
                rows + heads * key_dim * value_dim)}


def step(live_slots: float, weight_params: float, live_tokens: float,
         kv_bytes_per_token: int, state_bytes_per_slot: int) -> dict:
    """One decode step of the model: the weights it must read once (bf16:
    every layer's mixer, the dense feed-forward, the shared experts and
    routers, the routed experts that were HIT and not all that are held,
    the head; ``live_slots`` products each, which overstates the routed
    experts' operations and no bound: the step is bound by bytes), the held
    positions' latent rows in the latent layers, and the live slots' states
    read and written in the KDA layers.  Attention's own products and the
    rule's are not counted.  Means over steps may be passed: every term is
    linear."""
    return {"flops": 2.0 * live_slots * weight_params,
            "bytes": weight_params * costs.BF16
            + live_tokens * kv_bytes_per_token
            + 2.0 * live_slots * state_bytes_per_slot}
