"""Layer ``kernels``: least time over measured time, in %, of the routed
experts' grouped matmuls for the rows each held expert got in the traced units'
own batches (``flops_mla_moe.experts`` at this configuration's widths; the
configuration file gives ``expert_ffn_hidden_size`` under the older reader's
``moe_intermediate_size`` too). ``experts_roofline``'s reader under the name the
manifest lists for this cell."""

from perfbench.layer_metrics.experts_roofline import read  # noqa: F401
