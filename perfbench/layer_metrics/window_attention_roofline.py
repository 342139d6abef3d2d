"""Layer ``kernels``: least time over measured time, in %, of the region
``attn.window`` for the pairs the equations require, whatever implements them
(``perfbench/flops_window_moe.py``: ``4 * head_dim`` operations a pair and query
head over ``T W - W (W - 1) / 2`` pairs; q, k, v and the output once). A kernel
that visits whole tiles computes more and reads lower; a causal call in the
window's place reads an eighth."""

from perfbench.layer_metrics import _regions


def read(reading):
    return _regions.roofline(reading, "attn.window", "window_attention")
