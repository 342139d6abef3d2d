"""Operations and bytes for a model of Mamba-2 mixers beside causal attention,
computed from shapes: the model's per token for ``forward_ssm`` jobs, each
mixer part's per sequence and layer for its roofline, and what a chunked form
of the recurrence performs at a given chunk. Beside ``perfbench/flops.py``,
whose rules hold: what the equations *require*, whatever implements them. What
a chunked form computes beyond the recurrence is not required work; it has a
count of its own here so that a reader can set the two side by side."""

from __future__ import annotations


def ssm_scan(seq: int, heads: int, head_dim: int, state: int, groups: int) -> tuple[float, float]:
    """One sequence through one layer's recurrence, between the convolution and
    the gated norm: ``dt x B^T`` into the state and ``S C`` out of it, ``4 P N`` a
    head and position (the decay's multiply and the ``D`` term are of a lower
    order and not counted). Bytes: x, B, C and dt in and y out, once, bf16."""
    return 4.0 * heads * head_dim * state * seq, 2.0 * seq * (2 * heads * head_dim + 2 * groups * state + heads)


def ssm_conv(seq: int, channels: int, taps: int) -> tuple[float, float]:
    """One sequence through one layer's causal depthwise convolution with its
    bias and SiLU: ``2 K`` operations a channel and position. Bytes: the packed
    ``[x | B | C]`` in and out, once, bf16."""
    return 2.0 * taps * channels * seq, 2.0 * 2 * channels * seq


def attention(seq: int, heads: int, kv_heads: int, head_dim: int) -> tuple[float, float]:
    """One sequence through one layer's causal attention between its projections:
    ``QK^T`` and ``PV``, ``4 d`` a pair of a query and a key it sees. Bytes: q and
    the output at ``heads``, k and v at ``kv_heads``, once, bf16."""
    return 4.0 * heads * head_dim * (seq * (seq + 1) // 2), 2.0 * (2 * heads + 2 * kv_heads) * seq * head_dim


def chunked_ops(seq: int, chunk: int, heads: int, head_dim: int, state: int, groups: int) -> float:
    """What the chunked (state-space duality) form of one layer's recurrence
    performs on one sequence at chunks of ``chunk`` positions, the last one
    padded: ``C B^T`` a group and chunk, the masked product with ``dt x`` a head,
    a chunk's summary and the entering state's part of the output a head, and the
    lower-triangular product over the chunks' summaries that makes the entering
    states. One chunk needs only the first two."""
    chunk = min(chunk, seq)
    n = -(-seq // chunk)
    within = n * (2.0 * chunk * chunk * state * groups + 2.0 * chunk * chunk * head_dim * heads)
    if n == 1:
        return within
    return within + n * 2 * (2.0 * chunk * state * head_dim * heads) + 2.0 * n * n * state * head_dim * heads


def layer_matmul_params(keys: dict, mixer: str) -> int:
    """Weights of one layer that take part in a matmul: the mixer's projections
    (``in_proj`` to ``[z | x | B | C | dt]`` and ``out_proj``, or q, k, v and the
    output) and the SwiGLU's three. The convolution's taps multiply no matrix."""
    c, mlp = keys["hidden_size"], 3 * keys["hidden_size"] * keys["shared_intermediate_size"]
    if mixer == "mamba":
        inner = keys["mamba_n_heads"] * keys["mamba_d_head"]
        packed = 2 * inner + 2 * keys["mamba_n_groups"] * keys["mamba_d_state"] + keys["mamba_n_heads"]
        return c * packed + inner * c + mlp
    d = c // keys["num_attention_heads"]
    return c * d * (keys["num_attention_heads"] + 2 * keys["num_key_value_heads"]) + c * d * keys["num_attention_heads"] + mlp


def forward_flops_per_token(keys: dict, seq: int, last: int) -> float:
    """One forward pass of the first ``num_hidden_layers`` layers with the tied
    head on the last ``last`` positions, a token of the ``seq``: two operations
    for each weight a token meets (the embedding is a gather), the head's for the
    share of the positions it runs on, causal attention's and the recurrence's
    required operations."""
    mixers = keys["layer_types"][: keys["num_hidden_layers"]]
    weights = sum(layer_matmul_params(keys, m) for m in mixers)
    scan = ssm_scan(seq, keys["mamba_n_heads"], keys["mamba_d_head"], keys["mamba_d_state"], keys["mamba_n_groups"])[0]
    attend = attention(seq, keys["num_attention_heads"], keys["num_key_value_heads"],
                       keys["hidden_size"] // keys["num_attention_heads"])[0]
    mixing = mixers.count("mamba") * scan + mixers.count("attention") * attend
    return 2.0 * weights + 2.0 * keys["vocab_size"] * keys["hidden_size"] * last / seq + mixing / seq
