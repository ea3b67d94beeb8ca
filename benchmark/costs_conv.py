"""Operations and bytes of gated short-convolution layers (LFM2's operator,
``ray_tpu/models/llama.py::_conv_operator``: ``[B | C | z] = x W_in``, ``u = B
* z``, a causal depthwise convolution of ``u`` over ``taps`` positions, ``y =
C * conv(u)``, ``y W_out``), a position and a step, computed from shapes and
from what the engine's regions say the traced prefills and steps held.

As in ``costs_linear``: a prefill is counted for the prompt's REAL positions,
never the padded rung's, and a step for the slots that were LIVE, so a
program that computes or moves more than it must reads honestly low and
nothing here can pass 100%.
"""

from __future__ import annotations

from benchmark import costs


def operator_params(hidden: int, taps: int) -> int:
    """A layer's operator: ``W_in`` [D, 3D], the taps [taps, D], ``W_out``
    [D, D]."""
    return 4 * hidden * hidden + taps * hidden


def operator_position(hidden: int, taps: int) -> float:
    """Operations of one position in one layer: the two projections (2 a
    parameter), the convolution's ``taps`` multiply-adds a channel and the
    two gates' products."""
    return 8.0 * hidden * hidden + 2.0 * taps * hidden + 2.0 * hidden


def operator_prefill(positions: float, layers: int, hidden: int,
                     taps: int) -> dict:
    """The operator over ``positions`` real positions of one prompt in
    ``layers`` layers: the operations above; the bytes are each layer's
    operator read once a prompt, and a position's normed input read and its
    output written (bf16).  ``B``, ``C``, ``z`` and ``u`` live between the
    two products and are not counted: a program that writes them to memory
    reads low."""
    return {"flops": positions * layers * operator_position(hidden, taps),
            "bytes": layers * costs.BF16 * (
                operator_params(hidden, taps) + 2.0 * positions * hidden)}


def operator_step(live_slots: float, layers: int, hidden: int,
                  taps: int) -> dict:
    """One decode step of ``live_slots`` slots in ``layers`` layers: a
    position's operations a slot; each layer's operator read once (bf16)
    and every live slot's tail (``taps - 1`` positions of ``hidden`` bf16
    values) read once and written once.  Means over steps may be passed:
    both terms are linear."""
    return {"flops": live_slots * layers * operator_position(hidden, taps),
            "bytes": layers * costs.BF16 * (
                operator_params(hidden, taps)
                + 2.0 * live_slots * (taps - 1) * hidden)}
