"""``full_attention_ms`` (the region ``attn.full``) under the name the manifest
lists for ``granite-4.0-h-micro.fwd-t16k``: causal attention at head 64 without
rope, the four attention layers together."""

from perfbench.layer_metrics.full_attention_ms import read  # noqa: F401
