"""Share of the traced window's prefills whose rung ran its causal attention
through the flash forward kernel: ``attention`` of ``rt:engine.prefill``
("flash" or "dense").  A program whose regions do not carry the attribute
(the parent of the PR that added it) gives nothing to read."""

from benchmark import host_regions


def read(run):
    prefills = [p for p in host_regions.rows(run, "engine.prefill") or ()
                if "attention" in p]
    if not prefills:
        return None
    return 100.0 * sum(p["attention"] == "flash" for p in prefills) \
        / len(prefills)
