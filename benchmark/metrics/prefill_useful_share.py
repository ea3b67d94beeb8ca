"""Prompt positions over the positions the padded prefill ran, summed over
the traced window's prefills: ``prompt_len`` and ``padded_len`` of
``rt:engine.prefill``."""

from benchmark import host_regions


def read(run):
    prefills = host_regions.rows(run, "engine.prefill")
    if not prefills:
        return None
    return 100.0 * sum(p["prompt_len"] for p in prefills) \
        / sum(p["padded_len"] for p in prefills)
