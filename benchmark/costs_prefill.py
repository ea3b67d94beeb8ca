"""The model operations of one served prefill, computed from shapes and from
what the engine's regions say of it: what the chip HAS to compute for the
prompt's real positions, whatever rung the program padded them to.

* matrices met by every position (the operators' projections, a dense
  feed-forward, the routers): 2 operations a parameter a REAL position;
* routed experts: 6 x hidden x width an ASSIGNMENT (``rt:engine.prefill.moe``:
  gate, up and down of one SwiGLU expert for one token), never the experts
  that were not chosen;
* causal attention in the layers that have it: the score and the value
  products over the lower triangle, 2 x n^2 x heads x head_dim a layer for n
  real positions (half of full attention's 4 n^2: the masked half is not
  required);
* the head on ONE position (the prompt's last): 2 x hidden x vocabulary.

The padded tail of a rung, the dispatch's sort and copies, norms, gates and
the convolution's taps are not counted: a share of the chip's peak computed
from this (``prefill_mfu``) cannot pass 100% and reads low by what the
program computes beside it.
"""

from __future__ import annotations


def model_operations(positions: float, assignments: float,
                     matrix_params: float, expert_params: float,
                     attention_layers: int, heads: int, head_dim: int,
                     head_params: float, squared_positions: float = None
                     ) -> float:
    """Operations of prefills whose real positions sum to ``positions`` and
    whose (token, expert) pairs sum to ``assignments``: ``matrix_params``
    are met by every position, ``expert_params`` by every assignment (one
    expert's three matrices), ``head_params`` once a prefill.
    ``squared_positions`` is the sum of the prefills' n^2 (one prefill:
    ``positions`` squared, the default), for attention's triangle; pass a
    mean of each to get a mean prefill."""
    if squared_positions is None:
        squared_positions = positions * positions
    return 2.0 * positions * matrix_params \
        + 2.0 * assignments * expert_params \
        + 2.0 * squared_positions * attention_layers * heads * head_dim \
        + 2.0 * head_params
