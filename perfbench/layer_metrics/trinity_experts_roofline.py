"""Layer ``kernels``: least time over measured time, in %, of the routed
experts' grouped matmuls for the rows each expert got in the traced units' own
batches (``flops_mla_moe.experts`` at this configuration's widths).
``experts_roofline``'s reader under the name the manifest lists for this cell."""

from perfbench.layer_metrics.experts_roofline import read  # noqa: F401
