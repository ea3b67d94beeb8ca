"""Operations and bytes of a mixture-of-experts feed-forward's grouped
matmuls (SwiGLU experts: gate, up and down), computed from what was routed.

The bytes are those of the experts that were TOUCHED, never of all of them:
a path that reads only the experts its tokens chose cannot read above 100%
of the roofline, and one that reads every expert reads honestly low.  They
are counted in the type the program STORES the experts in, which is what
its kernels read (float32 today; the engine's ``rt:engine.decode.moe``
regions say it as ``weight_itemsize``): a program that comes to store them
in two bytes halves its bytes and its time together, and the share of the
roofline says how well the kernel moves what it has to move, not how far
the storage is from the compute type.
"""

from __future__ import annotations


def grouped_matmuls(assignments: int, experts_touched: int, hidden: int,
                    width: int, weight_itemsize: int) -> dict:
    """``assignments`` (token, expert) pairs over ``experts_touched``
    distinct (layer, expert) weight sets, experts of ``hidden`` x
    ``width`` stored at ``weight_itemsize`` bytes a parameter: each
    assignment is three products of 2*hidden*width operations; each touched
    expert's three matrices are read once as they are stored.  The
    activations (a few rows an expert) are not counted."""
    return {"flops": assignments * 6.0 * hidden * width,
            "bytes": experts_touched * 3.0 * hidden * width
            * weight_itemsize}
