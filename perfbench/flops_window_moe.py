"""Operations and bytes for attention within a window beside global attention,
and for a model of both whose later layers route to experts that are all held
here (``forward_window_moe`` jobs), computed from shapes: the model's per token,
each attention kind's per sequence and layer for its roofline, and the window
call's for the family ``attn_window_fwd``. Beside ``perfbench/flops.py``, whose
rules hold: what the equations *require*, whatever implements them. A tile the
kernel visits beyond the window, or a causal call in a window's place, is not
required work."""

from __future__ import annotations

from perfbench.flops import _prod


def window_pairs(seq: int, window: int) -> int:
    """(query, key) pairs with ``0 <= i - j < window``: ``T W - W (W - 1) / 2``,
    the causal triangle where the sequence is no longer than the window."""
    w = min(window, seq)
    return seq * w - w * (w - 1) // 2


def attention(seq: int, heads: int, kv_heads: int, head_dim: int, window: int | None = None) -> tuple[float, float]:
    """One sequence through one attention layer, between its projections:
    ``QK^T`` and ``PV``, ``4 d`` a pair and query head, over the pairs the mask
    keeps (all ``j <= i`` without a window); the softmax is not counted. Bytes:
    q and the output at ``heads``, k and v at ``kv_heads``, once, bf16."""
    pairs = window_pairs(seq, window if window is not None else seq)
    return 4.0 * heads * head_dim * pairs, 2.0 * (2 * heads + 2 * kv_heads) * seq * head_dim


def attention_params(keys: dict) -> int:
    """q, k, v, the gate (as wide as q) and ``o_proj`` of one layer."""
    c, h, g, d = keys["hidden_size"], keys["num_attention_heads"], keys["num_key_value_heads"], keys["head_dim"]
    return c * d * (h + 2 * g) + 2 * c * d * h


def forward_flops_per_token(keys: dict, seq: int, last: int) -> float:
    """One forward pass of the first ``num_hidden_layers`` layers with the head
    on the last ``last`` positions, a token of the ``seq``: two operations for
    each weight a token meets (the embedding is a gather): every layer's
    projections; the dense MLP in the first ``num_dense_layers``; in the others
    the router, the shared expert and ``num_experts_per_tok`` experts, which is
    exact and no mean here, every expert being held; the head's for the share
    of the positions it runs on; and both attention kinds' required pairs."""
    c, depth, dense = keys["hidden_size"], keys["num_hidden_layers"], keys["num_dense_layers"]
    kinds = keys["layer_types"][:depth]
    assert kinds.count("sliding_attention") + kinds.count("full_attention") == depth, kinds
    expert = 3 * c * keys["moe_intermediate_size"]
    weights = (depth * attention_params(keys) + dense * 3 * c * keys["intermediate_size"]
               + (depth - dense) * (keys["num_experts"] * c
                                    + (keys["num_shared_experts"] + keys["num_experts_per_tok"]) * expert))
    shape = (seq, keys["num_attention_heads"], keys["num_key_value_heads"], keys["head_dim"])
    mixing = (kinds.count("sliding_attention") * attention(*shape, keys["sliding_window"])[0]
              + kinds.count("full_attention") * attention(*shape)[0])
    return 2.0 * weights + 2.0 * keys["vocab_size"] * c * last / seq + mixing / seq


def attn_window_fwd(q: list[int], steps: list[int]) -> tuple[float, float]:
    """The family ``attn_window_fwd``: splash's forward under a local mask, told
    from a causal call by its mask-info operand, whose last dimension is the key
    tiles a query tile visits (``steps``: (heads or 1, query tiles, key tiles
    visited)) where a causal call's is every key tile. The window is not in the
    text; a query tile that visits ``s`` key tiles of ``b = T / query tiles``
    keys has a window in ``((s - 2) b, (s - 1) b]``, and the cost is for the
    upper end, which is the window itself where the tile divides it (2048 under
    tiles of 1024) and never more than the visited tiles. Reads q, k, v and
    writes the output once, bf16 (k and v arrive expanded to the query heads)."""
    n, t, d = _prod(q[:-2]), q[-2], q[-1]
    tile = t // steps[-2]
    window = max(steps[-1] - 1, 1) * tile
    return 4.0 * n * d * window_pairs(t, window), 4.0 * n * t * d * 2
