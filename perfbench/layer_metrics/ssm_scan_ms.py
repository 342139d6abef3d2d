"""Layer ``kernels``: device ms a call of every instruction, Mosaic call or
fusion, in the region ``ssm.scan`` (the state-space recurrence with its ``D``
term and the step's softplus, between the convolution and the gated norm, all
the Mamba-2 layers together)."""

from perfbench.layer_metrics import _regions


def read(reading):
    return _regions.region_ms(reading, "ssm.scan")
