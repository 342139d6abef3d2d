"""Operations and bytes, computed from shapes: the model's per token, and each
kernel family's per call. Kept with the benchmark so that no PR that claims a
gain can change the yardstick.

Model FLOPs follow the `on-chip-measurement` guide: what the forward and the
backward pass *require*, recomputation not counted. A matmul of an (n, k) by a
(k, m) matrix is 2*n*k*m operations. Which weights take part in a matmul is
the job's to say, since it knows its model (``perfbench/jobs/gpt_model.py``
counts the leaves of the parameter tree: the embedding is a gather and has no
matmul, the output head has).
"""

from __future__ import annotations


def attention_flops_per_token(heads: int, head_size: int, depth: int, seq: int) -> float:
    """Forward, causal: QK^T and PV are 2*T*hs each per head and query
    position over the whole context, and the mask leaves half of it."""
    return depth * 2.0 * seq * heads * head_size


def forward_flops_per_token(matmul_params: int, heads: int, head_size: int, depth: int, seq: int) -> float:
    """One forward pass: two operations for each weight that takes part in a
    matmul, and the attention. What a job makes of it is the job's: a training
    step is three of these (the backward pass is a gradient for each operand
    of each matmul), a forward call one (``perfbench/jobs/``)."""
    return 2.0 * matmul_params + attention_flops_per_token(heads, head_size, depth, seq)


# -----------------------------------------------------------------------------
# Kernel families. Each function takes the integers the family's pattern in
# ``layer_metrics/kernel_families/`` captured from the instruction's own
# text, and returns (operations, bytes) the *call* needs, given its operands.
# -----------------------------------------------------------------------------


def _prod(dims) -> int:
    n = 1
    for d in dims:
        n *= d
    return n


def _attention_call(q: list[int]) -> tuple[int, int, int]:
    """(batch*heads, T, hs) of a query operand (B, H, T, hs) or (H, T, hs)."""
    return _prod(q[:-2]), q[-2], q[-1]


def flash_fwd(q: list[int]) -> tuple[float, float]:
    """Causal attention forward over equal query and key lengths: two matmuls
    over half the (T, T) square. Reads q, k, v and writes the output in bf16,
    and one float32 log-sum-exp per row."""
    n, t, hs = _attention_call(q)
    return 2.0 * n * t * t * hs, 4.0 * n * t * hs * 2 + n * t * 4


def flash_bwd(q: list[int]) -> tuple[float, float]:
    """Backward from the saved output and log-sum-exp: the probabilities are
    not an input, so the call recomputes QK^T and then forms dV, dP, dK, dQ:
    five matmuls over the causal half. Reads q, k, v, o, do; writes dq, dk, dv."""
    n, t, hs = _attention_call(q)
    return 5.0 * n * t * t * hs, 8.0 * n * t * hs * 2 + 2 * n * t * 4


def cross_entropy_fwd(rows: list[int], vocab: list[int]) -> tuple[float, float]:
    """One read of the float32 logits (max, exp, sum, pick: about four
    operations an element), one float32 loss a row out."""
    n, v = rows[0], vocab[0]
    return 4.0 * n * v, n * v * 4.0 + n * 8.0


def cross_entropy_bwd(rows: list[int], vocab: list[int]) -> tuple[float, float]:
    """softmax minus one-hot, scaled: the logits read once and a gradient of
    the same shape written once, float32 both."""
    n, v = rows[0], vocab[0]
    return 4.0 * n * v, 2.0 * n * v * 4.0 + n * 8.0


def rope(x: list[int], cos: list[int]) -> tuple[float, float]:
    """x*cos + rotate_half(x)*sin: three operations an element, x read and
    written once in bf16, cos and sin read once."""
    n = _prod(x)
    return 3.0 * n, 2.0 * n * 2 + 2.0 * _prod(cos) * 2


def least_seconds(ops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """The roofline: the larger of operations over peak FLOP/s and bytes over
    peak bytes/s, and which of the two binds."""
    by_flops = ops / peaks["bf16_flops_per_s"]
    by_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (by_flops, "compute") if by_flops >= by_bytes else (by_bytes, "memory")
