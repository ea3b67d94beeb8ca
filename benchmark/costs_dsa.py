"""Operations and bytes of learned sparse attention over latent pages
(DeepSeek Sparse Attention) in a decode step, computed from what the live
sequences hold and what their queries keep, whatever implements the three
scopes ``dsa_index``, ``dsa_select`` and ``dsa_read``.

A step has to move, a layer: the indexer's matrices once (they are weights,
read whatever the batch), the indexer's KEY of every position the live
sequences hold (each is scored), and the latent ROW of every position a
query keeps (at most ``index_topk`` a sequence), as the pools store them.
Never the pages a sequence has reserved, never a latent row that was not
selected: a read that gathers more reads honestly low, and none can read
above 100%.  The selection itself moves nothing that the scores did not
already bring in (the least is a pass over scores that sit in fast memory),
so it adds no bytes: its time counts against the other two.
"""

from __future__ import annotations


def selection(live_positions: float, selected_positions: float, layers: int,
              heads: int, rank: int, rope: int, index_heads: int,
              index_dim: int, row_bytes: int, key_bytes: int,
              index_params: float = 0.0, itemsize: int = 2) -> dict:
    """``live_positions`` held and ``selected_positions`` kept (each summed
    over the step's sequences) in each of ``layers`` layers: an index head's
    score against a key is ``index_dim`` multiply-adds; an attention head's
    score against a selected row ``rank + rope`` and its weighing of the
    row's compressed part ``rank`` more; ``index_params`` the indexer's
    matrices of one layer.  The queries, the probabilities and the results
    (a few rows a sequence) are not counted."""
    return {"flops": layers * (
                live_positions * index_heads * 2.0 * index_dim
                + selected_positions * heads * 2.0 * ((rank + rope) + rank)),
            "bytes": layers * (live_positions * float(key_bytes)
                               + selected_positions * float(row_bytes)
                               + index_params * float(itemsize))}


def step(active: float, weight_params: float, live_positions: float,
         selected_positions: float, shape: dict, itemsize: int = 2) -> dict:
    """The WHOLE token step of ``active`` sequences: every weight it reads
    once (``weight_params``, the indexer's among them, the routed experts
    that were hit) and two operations a weight a sequence, and the
    selection's keys and rows (``selection`` without the indexer's matrices,
    which ``weight_params`` already holds)."""
    sparse = selection(live_positions, selected_positions,
                       **{**shape, "index_params": 0.0})
    return {"flops": 2.0 * active * weight_params + sparse["flops"],
            "bytes": weight_params * float(itemsize) + sparse["bytes"]}
