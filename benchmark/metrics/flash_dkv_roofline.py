"""The flash-attention dK and dV kernel's (``flash_dkv``) share of its
roofline: the least time the chip could take for its calls over their
device time."""

from benchmark import host_regions


def read(run):
    return host_regions.flash_kernel_roofline(run, "dkv")
