"""Layer ``experts``: device ms a call of every instruction, Mosaic call or
fusion, in the regions ``moe.route``, ``moe.experts`` and ``moe.zero``: the
routed layer on the shortcut with its router, its dispatch both ways, its
grouped matmuls and the zero-compute term, all layers together."""

from perfbench.layer_metrics import _regions


def read(reading):
    return _regions.region_ms(reading, "moe.")
