"""Operations and bytes of a decode step that denoises a block of positions
a sequence (``ray_tpu/models/llama.py::llama_block_step``), computed from
what the engine's regions say the steps held and touched.

As in ``costs_moe``, the experts' bytes are those of the experts a step
TOUCHED, in the type the program stores them in, and the cached positions
are those the stepped sequences HELD (``live_tokens``: what is committed
and the block), never what the paged read gathered: a step that reads more
than it must reads honestly low, and nothing here can pass 100%.
"""

from __future__ import annotations

from benchmark import costs, costs_moe


def block_read(live_tokens: int, kv_bytes_per_token: int) -> dict:
    """The paged read of the keys and values of ``live_tokens`` cached
    positions, every layer's (``kv_bytes_per_token`` is all layers'): a
    gather, no operations."""
    return {"flops": 0.0, "bytes": float(live_tokens * kv_bytes_per_token)}


def block_step(rows: int, weight_params: int, assignments: int,
               experts_touched: int, hidden: int, width: int,
               weight_itemsize: int, live_tokens: int,
               kv_bytes_per_token: int) -> dict:
    """One block step over ``rows`` positions (slots x block length): the
    matrices every step reads whatever it routes (``weight_params``:
    attention, router, head; bf16, read once, ``rows`` products each), the
    touched experts' grouped matmuls, and the held positions' keys and
    values.  Attention's own products (a few rows against the cache) are
    not counted.  Means over steps may be passed: every term is linear."""
    experts = costs_moe.grouped_matmuls(assignments, experts_touched,
                                        hidden, width, weight_itemsize)
    cached = block_read(live_tokens, kv_bytes_per_token)
    return {"flops": experts["flops"] + 2.0 * rows * weight_params,
            "bytes": weight_params * costs.BF16 + experts["bytes"]
            + cached["bytes"]}
