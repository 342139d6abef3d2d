"""Layer ``kernels``: least time over measured time, in %, of the
latent-attention forward calls: causal, ``T*T*(d_qk + d_v)`` operations a
head (``perfbench/flops_mla_moe.py``)."""

from perfbench.reading import kernel_family_table


def read(reading):
    row = kernel_family_table(reading).get("attn_mla_fwd")
    return row["roofline_pct"] if row else None
