"""Operations for a model of double layers: two latent-attention sublayers and
two dense FFNs a layer with one routed layer on a shortcut across them, whose
router has zero-compute experts beside the real ones of which this chip holds a
share (``forward_scmoe`` jobs). Computed from shapes, beside ``perfbench/flops.py``,
whose rules hold: what the equations *require*, a matmul of (n, k) by (k, m) is
2*n*k*m operations. The latent-attention call's own cost is the family
``attn_mla_fwd``'s and the grouped matmuls' ``flops_mla_moe.experts``: the same
kernels as ``a.x-k1.fwd``'s."""

from __future__ import annotations


def attention_matmul_params(keys: dict) -> int:
    """Weights of one latent-attention sublayer that take part in a matmul."""
    h, c = keys["num_attention_heads"], keys["hidden_size"]
    dn, dr, dv = keys["qk_nope_head_dim"], keys["qk_rope_head_dim"], keys["v_head_dim"]
    return (c * keys["q_lora_rank"] + keys["q_lora_rank"] * h * (dn + dr) + c * (keys["kv_lora_rank"] + dr)
            + keys["kv_lora_rank"] * h * (dn + dv) + h * dv * c)


def router_outputs(keys: dict) -> int:
    return keys["n_routed_experts_published"] + keys["zero_expert_num"]


def expert_params(keys: dict) -> int:
    """One routed expert: three matrices."""
    return 3 * keys["hidden_size"] * keys["expert_ffn_hidden_size"]


def routed_here_per_token(keys: dict) -> float:
    """Routed experts a token computes here when the router is even over all its
    outputs, the zero-compute ones among them: ``moe_topk`` times the held share."""
    return keys["moe_topk"] * keys["n_routed_experts"] / router_outputs(keys)


def layer_flops_per_token(keys: dict, seq: int) -> dict:
    """One double layer, a token of a sequence of ``seq``, by part: two
    operations for each weight of both sublayers' projections and dense FFNs and
    of the router; the causal scores, ``T (d_qk + d_v)`` a head, query position
    and sublayer; the experts here by the ``routed_here_per_token`` an even
    router sends, a constant, not the held experts' sum; and the zero-compute
    term, one multiply-add an element of the token's row."""
    c = keys["hidden_size"]
    d_qk = keys["qk_nope_head_dim"] + keys["qk_rope_head_dim"]
    return {"projections": 2 * 2.0 * attention_matmul_params(keys),
            "scores": 2.0 * seq * keys["num_attention_heads"] * (d_qk + keys["v_head_dim"]),
            "dense_ffns": 2 * 2.0 * 3 * c * keys["ffn_hidden_size"],
            "router": 2.0 * router_outputs(keys) * c,
            "experts": 2.0 * routed_here_per_token(keys) * expert_params(keys),
            "zero": 2.0 * c}


def forward_flops_per_token(keys: dict, seq: int, last: int) -> float:
    """One forward pass of ``num_layers`` double layers with the head on the last
    ``last`` positions, a token of the ``seq`` (the embedding is a gather)."""
    return (keys["num_layers"] * sum(layer_flops_per_token(keys, seq).values())
            + 2.0 * keys["vocab_size"] * keys["hidden_size"] * last / seq)
