"""Operations a token for a model whose layers mix by a gated short
convolution or by grouped-query attention and whose later layers route to
experts that are all held here (``forward_conv_moe`` jobs). Beside
``perfbench/flops.py``, whose rules hold: what the pass *requires*, two
operations for every weight a token meets, the embedding is a gather."""

from __future__ import annotations


def conv_mixer_params(keys: dict) -> int:
    """``in_proj`` (hidden to 3 hidden) and ``out_proj``; the taps are counted apart."""
    return 4 * keys["hidden_size"] ** 2


def attention_params(keys: dict) -> int:
    """q, k, v projections and ``out_proj`` of one grouped-query layer."""
    c, h, g = keys["hidden_size"], keys["num_attention_heads"], keys["num_key_value_heads"]
    return c * (h + 2 * g) * (c // h) + c * c


def forward_flops_per_token(keys: dict, seq: int) -> float:
    """One forward pass of the first ``num_hidden_layers`` layers and the head.
    Matmuls: each mixer's projections; the dense MLP in the first
    ``num_dense_layers``; in the others the router and ``num_experts_per_tok``
    experts, which is exact and no mean here, every expert being held; the head
    (the embedding table once more). Attention proper, causal: ``T * 2 * head``
    a head and query position. The taps: a multiply and an add a tap and channel."""
    c, depth, dense = keys["hidden_size"], keys["num_hidden_layers"], keys["num_dense_layers"]
    mixers = keys["layer_types"][:depth]
    convs, attns = mixers.count("conv"), mixers.count("full_attention")
    assert convs + attns == depth, mixers
    weights = (convs * conv_mixer_params(keys) + attns * attention_params(keys)
               + dense * 3 * c * keys["intermediate_size"]
               + (depth - dense) * (keys["num_experts"] * c
                                    + keys["num_experts_per_tok"] * 3 * c * keys["moe_intermediate_size"])
               + keys["vocab_size"] * c)
    attention = attns * seq * keys["num_attention_heads"] * 2 * (c // keys["num_attention_heads"])
    taps = convs * 2 * keys["conv_L_cache"] * c
    return 2.0 * weights + attention + taps
