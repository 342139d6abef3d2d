"""Layer ``kernels``: device ms a call of every instruction, Mosaic call or
fusion, in the region ``attn.linear`` (the decayed linear attention between the
linear layers' projections, norm and gate)."""

from perfbench.layer_metrics import _regions


def read(reading):
    return _regions.region_ms(reading, "attn.linear")
