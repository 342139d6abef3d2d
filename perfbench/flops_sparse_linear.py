"""Operations and bytes for block-sparse attention whose blocks the data chooses
and for linear attention with a decay a head, computed from shapes: the model's
per token for ``forward_sparse_linear`` jobs and each mixer's per sequence and
layer for its roofline. Beside ``perfbench/flops.py``, whose rules hold: what
the equations *require*, whatever implements them. A masked dense form that
computes every score, or a chunked form's extra products, is not required work."""

from __future__ import annotations


def pooled_keys_before(t: int, kernel_size: int, kernel_stride: int) -> int:
    """Pooled keys wholly in the past of the query at position ``t`` (0-based):
    windows ``[stride j, stride j + kernel_size)`` with ``stride j + kernel_size <= t + 1``."""
    return (t + 1 - kernel_size) // kernel_stride + 1 if t + 1 >= kernel_size else 0


def keys_attended(t: int, block_size: int, topk: int) -> int:
    """Keys the query at ``t`` attends to: all ``t + 1`` while it has at most
    ``topk`` blocks, then ``topk - 1`` whole blocks and its own up to itself."""
    return t + 1 if t // block_size + 1 <= topk else (topk - 1) * block_size + t % block_size + 1


def sparse_attention(seq: int, heads: int, kv_heads: int, head_dim: int, sparse: dict) -> tuple[float, float]:
    """One sequence through one sparse layer, between its projections: the
    scores of every query head against its pooled keys (``2 d`` a pair) and
    attention proper over the chosen keys (``QK^T`` and ``PV``, ``4 d`` a pair);
    the softmaxes, the pooling and the choice are not counted. A sequence
    shorter than ``dense_len`` scores nothing and attends causally to all.
    Bytes: q and the output at ``heads``, k and v at ``kv_heads``, once, bf16."""
    if seq < sparse["dense_len"]:
        scored, attended = 0, seq * (seq + 1) // 2
    else:
        scored = sum(pooled_keys_before(t, sparse["kernel_size"], sparse["kernel_stride"]) for t in range(seq))
        attended = sum(keys_attended(t, sparse["block_size"], sparse["topk"]) for t in range(seq))
    return (2.0 * heads * head_dim * scored + 4.0 * heads * head_dim * attended,
            2.0 * (2 * heads + 2 * kv_heads) * seq * head_dim)


def linear_attention(seq: int, heads: int, head_dim: int) -> tuple[float, float]:
    """One sequence through one linear-attention layer, between its projections:
    the recurrence, ``k^T v`` into the state and ``q S`` out of it, ``4 d^2`` a
    head and position. Bytes: q, k, v and the output once, bf16."""
    return 4.0 * heads * head_dim ** 2 * seq, 2.0 * 4 * heads * seq * head_dim


def layer_matmul_params(keys: dict, mixer: str) -> int:
    """Weights of one layer that take part in a matmul: the mixer's projections
    (q, k, v, the gate where it has one, the output) and the SwiGLU's three."""
    c, d = keys["hidden_size"], keys["head_dim"]
    if mixer == "minicpm4":
        heads, kv, gate = keys["num_attention_heads"], keys["num_key_value_heads"], keys["attn_use_output_gate"]
    else:
        heads, kv, gate = keys["lightning_nh"], keys["lightning_nkv"], keys["use_output_gate"]
    return c * d * (heads + 2 * kv) + c * d * heads * (2 if gate else 1) + 3 * c * keys["intermediate_size"]


def forward_flops_per_token(keys: dict, seq: int, last: int) -> float:
    """One forward pass of the first ``num_hidden_layers`` layers with the head
    on the last ``last`` positions, a token of the ``seq``: two operations for
    each weight a token meets (the embedding is a gather), the head's for the
    share of the positions it runs on, and both mixers' required operations."""
    mixers = keys["mixer_types"][: keys["num_hidden_layers"]]
    weights = sum(layer_matmul_params(keys, m) for m in mixers)
    sparse = sparse_attention(seq, keys["num_attention_heads"], keys["num_key_value_heads"], keys["head_dim"],
                              keys["sparse_config"])[0]
    linear = linear_attention(seq, keys["lightning_nh"], keys["lightning_head_dim"])[0]
    mixing = mixers.count("minicpm4") * sparse + mixers.count("lightning-attn") * linear
    return 2.0 * weights + 2.0 * keys["vocab_size"] * keys["hidden_size"] * last / seq + mixing / seq
