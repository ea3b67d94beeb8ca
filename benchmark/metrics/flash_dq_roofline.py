"""The flash-attention dQ kernel's (``flash_dq``) share of its roofline:
the least time the chip could take for its calls over their device time."""

from benchmark import host_regions


def read(run):
    return host_regions.flash_kernel_roofline(run, "dq")
