"""Operations and bytes that the work requires, computed from shapes.

The yardstick beside ``peaks.json``: a roofline share is the least time
the chip could take for these, over the time the trace shows.  Recomputed
operations (rematerialisation) are never counted as required.
"""

from __future__ import annotations

BF16 = 2
F32 = 4


def train_flops_per_token(matmul_params: int, layers: int, seq: int,
                          embed: int) -> float:
    """Forward and backward of a dense decoder: 6 per parameter that sits
    in a matrix product (bench.py's arithmetic), and causal attention's
    score and value products, 6*L*S*E: half of the 12*L*S*E of full
    attention, since the masked half is not required."""
    return 6.0 * matmul_params + 6.0 * layers * seq * embed


def flash_pass(kind: str, batch: int, heads: int, seq: int, head_dim: int
               ) -> dict:
    """One causal flash-attention kernel call: ``fwd`` (scores, values),
    ``dq`` (scores again, dP, dQ) or ``dkv`` (scores again, dP, dV, dK).
    Each product is 2*B*N*S*S*H operations, halved by the causal mask;
    bytes are the bf16 operands read and results written once, and the
    f32 row statistics."""
    products = {"fwd": 2, "dq": 3, "dkv": 4}[kind]
    rows = batch * heads * seq
    tensors = {"fwd": 4, "dq": 5, "dkv": 6}[kind]     # q k v o | +do dq | ..
    stats = {"fwd": 1, "dq": 2, "dkv": 2}[kind]       # lse | lse, delta
    return {"flops": products * 2.0 * rows * seq * head_dim / 2,
            "bytes": tensors * rows * head_dim * BF16 + stats * rows * F32}


def least_seconds(cost: dict, peaks: dict) -> float:
    """The roofline: the larger of operations over peak FLOP/s and bytes
    over peak bytes/s."""
    return max(cost["flops"] / peaks["bf16_flops_per_s"],
               cost["bytes"] / peaks["hbm_bytes_per_s"])


def decode_step_bytes(weight_params: int, kv_bytes_per_token: int,
                      live_kv_tokens: float) -> float:
    """What one decode step must read: every weight once in the compute
    type (bf16), and the cached keys and values of the live contexts."""
    return weight_params * BF16 + kv_bytes_per_token * live_kv_tokens
