"""Layer ``kernels``: least time over measured time, in %, of the regions
``attn.sparse.*`` for the work the equations require, whatever implements them
(``perfbench/flops_sparse_linear.py``: every query head against the pooled keys
in its past, attention over the chosen blocks' keys; q, k, v and the output
once). A masked dense form computes every score and reads low."""

from perfbench.layer_metrics import _regions


def read(reading):
    return _regions.roofline(reading, "attn.sparse.", "sparse_attention")
