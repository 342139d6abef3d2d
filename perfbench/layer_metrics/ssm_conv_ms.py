"""Layer ``kernels``: device ms a call of every instruction in the region
``ssm.conv`` (the causal depthwise convolution over the packed ``[x | B | C]``
with its bias and SiLU, all the Mamba-2 layers together)."""

from perfbench.layer_metrics import _regions


def read(reading):
    return _regions.region_ms(reading, "ssm.conv")
