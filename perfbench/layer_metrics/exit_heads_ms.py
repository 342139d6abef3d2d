"""Layer ``kernels``: device ms a training step of every instruction, Mosaic
call or fusion, in the region ``exit.heads`` and in ``exit.heads/bwd``, what
the backward writes for it: every loop step's head matmul and per-token
cross-entropy, forward and backward, all the steps together."""

from perfbench.layer_metrics import _regions


def read(reading):
    return _regions.region_ms(reading, "exit.heads")
