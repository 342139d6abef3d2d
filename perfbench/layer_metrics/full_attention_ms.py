"""Layer ``kernels``: device ms a call of every instruction, Mosaic call or
fusion, in the region ``attn.full`` (causal attention over every key, between
the global layers' projections)."""

from perfbench.layer_metrics import _regions


def read(reading):
    return _regions.region_ms(reading, "attn.full")
