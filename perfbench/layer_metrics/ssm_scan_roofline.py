"""Layer ``kernels``: least time over measured time, in %, of the region
``ssm.scan`` for the work the equations require, whatever implements them
(``perfbench/flops_ssm.py``: the recurrence, ``4 P N`` a head and position; x, B,
C and dt in and y out once: bound by memory)."""

from perfbench.layer_metrics import _regions


def read(reading):
    return _regions.roofline(reading, "ssm.scan", "ssm_scan")
