"""Share of the traced window's decode steps whose program reads the paged
K/V through the kernel that walks the page table: ``paged_read`` of
``rt:engine.decode.dispatch`` ("kernel" or "gather": what the step's program
was compiled with, ``ray_tpu/ops/paged_attention.py::paged_read_kind``).  A
program whose regions do not carry the attribute (the parent of the PR that
added it) gives nothing to read."""

from benchmark import host_regions


def read(run):
    steps = [s for s in host_regions.rows(run, "engine.decode.dispatch")
             or () if "paged_read" in s]
    if not steps:
        return None
    return 100.0 * sum(s["paged_read"] == "kernel" for s in steps) \
        / len(steps)
