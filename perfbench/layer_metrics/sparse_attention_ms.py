"""Layer ``kernels``: device ms a call of every instruction, Mosaic call or
fusion, in the regions ``attn.sparse.*`` (the block selection and the attention
over the chosen blocks, between the sparse layers' projections)."""

from perfbench.layer_metrics import _regions


def read(reading):
    return _regions.region_ms(reading, "attn.sparse.")
