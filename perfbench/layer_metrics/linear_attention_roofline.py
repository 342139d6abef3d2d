"""Layer ``kernels``: least time over measured time, in %, of the region
``attn.linear`` for the work the equations require, whatever implements them
(``perfbench/flops_sparse_linear.py``: the recurrence, ``4 d^2`` a head and
position; q, k, v and the output once: bound by memory)."""

from perfbench.layer_metrics import _regions


def read(reading):
    return _regions.roofline(reading, "attn.linear", "linear_attention")
