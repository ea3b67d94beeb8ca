"""Tokens a block model's callers got per slot step: ``tokens`` of the
traced window's ``rt:engine.deliver`` regions (committed less the dropped
tails) over their ``denoise_slots`` + ``commit_slots``.  At a static
schedule of T passes a block of B it is B / (T + 1) less the tails; higher
when a confidence threshold unmasks more than the count.  ``None`` where no
delivery carries the block attributes: a model that yields one token a step."""

from benchmark import host_regions


def read(run):
    steps = [r for r in host_regions.rows(run, "engine.deliver") or ()
             if "commit_slots" in r]
    slots = sum(r["denoise_slots"] + r["commit_slots"] for r in steps)
    return sum(r["tokens"] for r in steps) / slots if slots else None
