"""Layer ``kernels``: least time over measured time, in %, of the regions
``exit.heads`` and ``exit.heads/bwd`` for the work every loop step's head
requires, whatever implements it (``perfbench/flops_loop.py``: ``6 T C V``
operations a loop step; the bf16 logits and their gradient each written once
and read twice, the head's weight read once: bound by compute)."""

from perfbench.layer_metrics import _regions


def read(reading):
    return _regions.roofline(reading, "exit.heads", "exit_heads")
