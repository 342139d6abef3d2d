"""Operations and bytes for latent attention and routed experts, computed from
shapes and counts: the model's per token for ``forward_mla_moe`` jobs, the
attention call's for the family ``attn_mla_fwd``, and the grouped matmuls'
from the rows that were routed here. Beside ``perfbench/flops.py``, whose
rules hold: what the passes *require*, a matmul of (n, k) by (k, m) is
2*n*k*m operations, a zero-padded lane is never work."""

from __future__ import annotations

from perfbench.flops import _prod


def attention_matmul_params(keys: dict) -> int:
    """Weights of one latent-attention layer that take part in a matmul."""
    h, c = keys["num_attention_heads"], keys["hidden_size"]
    dn, dr, dv = keys["qk_nope_head_dim"], keys["qk_rope_head_dim"], keys["v_head_dim"]
    return (c * keys["q_lora_rank"] + keys["q_lora_rank"] * h * (dn + dr) + c * (keys["kv_lora_rank"] + dr)
            + keys["kv_lora_rank"] * h * (dn + dv) + h * dv * c)


def expert_params(keys: dict) -> int:
    """One routed (or shared) expert: three matrices."""
    return 3 * keys["hidden_size"] * keys["moe_intermediate_size"]


def routed_here_per_token(keys: dict) -> float:
    """Routed experts a token computes here when the router is even: its
    ``num_experts_per_tok`` times the share of the published experts held."""
    return keys["num_experts_per_tok"] * keys["n_routed_experts"] / keys["n_routed_experts_published"]


def forward_flops_per_token(keys: dict, seq: int) -> float:
    """One forward pass of the share this chip holds. Two operations for each
    weight outside the routed experts (attention projections, the dense MLP,
    the router, the shared expert, the head; the embedding is a gather); for
    each expert layer two for each weight of the ``routed_here_per_token``
    experts an even router sends a token to here, a constant, not the held
    experts' sum; and the attention proper, causal, ``T * (d_qk + d_v)`` a
    head and query position."""
    c, depth, dense = keys["hidden_size"], keys["num_hidden_layers"], keys["first_k_dense_replace"]
    moe = depth - dense
    outside = (depth * attention_matmul_params(keys) + dense * 3 * c * keys["intermediate_size"]
               + moe * (keys["n_routed_experts_published"] * c + keys["n_shared_experts"] * expert_params(keys))
               + keys["vocab_size"] * c)
    routed = moe * routed_here_per_token(keys) * expert_params(keys)
    d_qk = keys["qk_nope_head_dim"] + keys["qk_rope_head_dim"]
    attention = depth * seq * keys["num_attention_heads"] * (d_qk + keys["v_head_dim"])
    return 2.0 * outside + 2.0 * routed + attention


def attn_mla_fwd(q: list[int], v: list[int]) -> tuple[float, float]:
    """Causal attention forward whose value heads are narrower than its query
    and key heads: ``QK^T`` is ``2*T*T*d_qk`` and ``PV`` ``2*T*T*d_v`` a head
    over the whole square, and the mask leaves half. Reads q and k at ``d_qk``
    and v at ``d_v``, writes the output at ``d_v``, in bf16, and one float32
    log-sum-exp a row."""
    n, t, d_qk = _prod(q[:-2]), q[-2], q[-1]
    d_v = v[-1]
    return 1.0 * n * t * t * (d_qk + d_v), n * t * (2 * d_qk + 2 * d_v) * 2 + n * t * 4


def experts(rows_per_expert, hidden: int, width: int) -> tuple[float, float]:
    """The three grouped matmuls of one expert layer for the rows that were
    routed to each held expert: ``2 * rows * 3 * hidden * width`` operations.
    Bytes, bf16: the three matrices of every expert that got a row, once; each
    row read at ``hidden`` twice (gate, up) and written at ``hidden`` once, its
    two ``width`` activations written and their product read."""
    rows = float(sum(rows_per_expert))
    busy = sum(1 for r in rows_per_expert if r > 0)
    return (2.0 * rows * 3 * hidden * width,
            2.0 * (busy * 3 * hidden * width + rows * (3 * hidden + 3 * width)))
