"""Operations and bytes for a looped model (Ouro's LoopLM), computed from
shapes: the model's per token for the ``train_loop`` job, and the exit heads'
least work a training step for their roofline. Beside ``perfbench/flops.py``,
whose rules hold: what the equations *require*, whatever implements them.

A looped model runs its layers and its head once a loop step, so each counts
``loop_steps`` times; the exit gate, a matmul of one output, is read on every
step but the last, whose share of the exit distribution is what is left."""

from __future__ import annotations

from perfbench import flops


def layer_matmul_params(keys: dict) -> int:
    """Weights of one layer that take part in a matmul: the packed q, k, v
    projection and the output's (heads of ``head_dim``), and the SwiGLU's three."""
    c, d = keys["hidden_size"], keys["head_dim"]
    heads, kv = keys["num_attention_heads"], keys["num_key_value_heads"]
    return c * d * (heads + 2 * kv) + heads * d * c + 3 * c * keys["intermediate_size"]


def forward_flops_per_token(keys: dict, seq: int) -> float:
    """One forward pass of the first ``num_hidden_layers`` layers, run
    ``total_ut_steps`` times, with a head a loop step and the gate on every
    step but the last: two operations for each weight a token meets (the
    embedding is a gather), and causal attention's at every layer of every step."""
    loops, depth, c = keys["total_ut_steps"], keys["num_hidden_layers"], keys["hidden_size"]
    weights = loops * (depth * layer_matmul_params(keys) + keys["vocab_size"] * c) + (loops - 1) * c
    return 2.0 * weights + flops.attention_flops_per_token(keys["num_attention_heads"], keys["head_dim"],
                                                           loops * depth, seq)


def exit_heads(tokens: int, hidden: int, vocab: int, loops: int) -> tuple[float, float]:
    """What the heads of every loop step require in one training step, forward
    and backward: ``6 T C V`` operations a loop step (the head's matmul and its
    two gradients; the cross-entropy's few operations an element are of a lower
    order and not counted). Bytes a loop step, bf16: the logits written once and
    read by the cross-entropy's forward and backward, their gradient written
    once and read by the head's two backward matmuls, and the head's weight read
    once."""
    per_loop_ops = 6.0 * tokens * hidden * vocab
    per_loop_bytes = 2.0 * (3 * tokens * vocab + 3 * tokens * vocab + vocab * hidden)
    return loops * per_loop_ops, loops * per_loop_bytes
