"""Committed tokens that reached nobody, as a share of the committed: the
last block's tail past ``max_new_tokens`` (``dropped_tail``) and the blocks
of sequences retired while their step was in flight (``dropped_stray``), on
the traced window's ``rt:engine.deliver`` regions."""

from benchmark import host_regions


def read(run):
    steps = [r for r in host_regions.rows(run, "engine.deliver") or ()
             if "dropped_tail" in r]
    dropped = sum(r["dropped_tail"] + r["dropped_stray"] for r in steps)
    committed = dropped + sum(r["tokens"] for r in steps)
    return 100.0 * dropped / committed if committed else None
