"""Operations and bytes of latent (MLA) attention's paged read in a decode
step, computed from what the live sequences hold.

The absorbed decode path scores every head against ONE cached row of a
position (the compressed key-value beside the shared rotated key) and weighs
the compressed part of the rows: no per-head key or value exists.  What it
has to move is the rows of the positions the live sequences HOLD, once a
layer, never the pages they have reserved: a read that gathers every
reserved page reads honestly low, and cannot read above 100%.
"""

from __future__ import annotations


def latent_read(live_positions: int, layers: int, rank: int, rope: int,
                heads: int, itemsize: int = 2) -> dict:
    """``live_positions`` cached positions (summed over sequences and steps)
    read in each of ``layers`` layers: a row of ``rank + rope`` values at
    ``itemsize`` bytes; a head's score against a row is ``rank + rope``
    multiply-adds and its weighing of the row's compressed part ``rank``
    more.  The queries, the probabilities and the result (a few rows a
    sequence) are not counted."""
    rows = live_positions * layers
    return {"flops": rows * heads * 2.0 * ((rank + rope) + rank),
            "bytes": rows * (rank + rope) * float(itemsize)}
