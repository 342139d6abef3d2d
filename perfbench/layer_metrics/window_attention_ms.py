"""Layer ``kernels``: device ms a call of every instruction, Mosaic call or
fusion, in the region ``attn.window`` (attention within the window, between the
window layers' projections, all of them together)."""

from perfbench.layer_metrics import _regions


def read(reading):
    return _regions.region_ms(reading, "attn.window")
