"""Layer ``kernels``: least time over measured time, in %, of the
latent-attention forward calls: causal, ``T*T*(d_qk + d_v)`` operations a head
(``perfbench/flops_mla_moe.py``). ``mla_attention_roofline``'s reader under the
name the manifest lists for this cell."""

from perfbench.layer_metrics.mla_attention_roofline import read  # noqa: F401
