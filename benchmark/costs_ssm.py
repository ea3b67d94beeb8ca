"""Operations and bytes of the state-space rule without a correction
(Mamba-2's, ``ray_tpu/ops/linear_attention.py``'s ``ssm_*``): the chunked
form over a prompt.  The token step's bytes are ``costs_linear.state_step``'s
as they stand (every live slot's float32 state read once and written once;
the rule needs 5 operations a value where the delta rule needs 7, and is
bound by the bytes either way).

As in ``costs_linear``: the scan is counted for a prompt's REAL length (whole
chunks of it), never the padded rung's: a program that computes more than it
must reads honestly low, and nothing here can pass 100%.
"""

from __future__ import annotations

from benchmark import costs

CHUNK = 128


def state_step(live_slots: float, layers: int, heads: int, key_dim: int,
               value_dim: int) -> dict:
    """One position of the rule for ``live_slots`` slots in ``layers``
    layers: every float32 state [heads, key_dim, value_dim] read once and
    written once; per value of it the decay (a multiply), the sum S^T q (a
    multiply and an add) and the update (outer product, add)."""
    values = live_slots * layers * heads * key_dim * value_dim
    return {"flops": 5.0 * values, "bytes": 2.0 * values * costs.F32}


def chunked_scan(length: int, layers: int, heads: int, key_dim: int,
                 value_dim: int, chunk: int = CHUNK) -> dict:
    """The chunked rule over a sequence of ``length`` real positions in
    ``layers`` layers, from an empty state.  A chunk of c positions: ONE c x
    c matrix of q . k over key_dim that all heads share (2 c c key_dim), and
    a head's decay over it (c c: an exponential and a multiply, counted as
    2), its product with the values (2 c c value_dim), the read of the state
    before the chunk (2 c key_dim value_dim) and what the chunk writes to it
    (2 c key_dim value_dim, and the state's own decay).  Bytes: x, the decay
    and the step size a head, the one key and query read and y written once,
    float32, and the state written once."""
    chunks = -(-length // chunk)
    shared = 2.0 * chunk * chunk * key_dim
    per_head = 2.0 * chunk * chunk + 2.0 * chunk * chunk * value_dim \
        + 4.0 * chunk * key_dim * value_dim + key_dim * value_dim
    rows = length * (heads * (2 * value_dim + 2) + 2 * key_dim)
    return {"flops": layers * chunks * (shared + heads * per_head),
            "bytes": layers * costs.F32 * (
                rows + heads * key_dim * value_dim)}
