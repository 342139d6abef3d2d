"""Operations and bytes of the cross-entropy kernels on bfloat16 logits, for
the families ``cross_entropy_fwd_bf16`` and ``cross_entropy_bwd_bf16``. Beside
``perfbench/flops.py``, whose float32 families they stand next to and whose
rules hold: what the call *requires*, given its operands. Since PR 28 the
training cells' loss reads the logits as the head wrote them, so the same two
calls move half the bytes; the operations are the float32 families' (the
kernels upcast in VMEM), and the per-row target and loss are counted as there."""

from __future__ import annotations

_LOGIT_BYTES = 2


def cross_entropy_fwd_bf16(rows: list[int], vocab: list[int]) -> tuple[float, float]:
    """One read of the bfloat16 logits (max, exp, sum, pick: about four
    operations an element), one float32 loss a row out."""
    n, v = rows[0], vocab[0]
    return 4.0 * n * v, n * v * float(_LOGIT_BYTES) + n * 8.0


def cross_entropy_bwd_bf16(rows: list[int], vocab: list[int]) -> tuple[float, float]:
    """softmax minus one-hot, scaled: the logits read once and a gradient of
    the same shape written once, bfloat16 both."""
    n, v = rows[0], vocab[0]
    return 4.0 * n * v, 2.0 * n * v * float(_LOGIT_BYTES) + n * 8.0
