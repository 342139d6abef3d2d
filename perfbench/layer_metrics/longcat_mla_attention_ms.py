"""Layer ``kernels``: device ms a call in the latent-attention forward calls (the
family ``attn_mla_fwd``), eight of 16,384 positions a call of this cell's four
double layers. ``mla_attention_ms``'s reader under the name the manifest lists
for this cell (the older entry's ``workloads`` is not this PR's to lengthen)."""

from perfbench.layer_metrics.mla_attention_ms import read  # noqa: F401
