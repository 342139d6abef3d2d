"""Layer ``kernels``: least time over measured time, in %, of the region
``ssm.conv`` for the work the equations require (``perfbench/flops_ssm.py``:
``2 K`` operations a channel and position; the packed ``[x | B | C]`` in and out
once: bound by memory)."""

from perfbench.layer_metrics import _regions


def read(reading):
    return _regions.roofline(reading, "ssm.conv", "ssm_conv")
