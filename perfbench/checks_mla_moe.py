"""The limits of the comparison that decides ``correct`` for the
``forward_mla_moe`` job: logits of the last ``checks.LOGIT_POSITIONS``
positions of one seeded sequence, the system (bf16 weights and activations,
float32 accumulation and router) against the float32 reference
(``perfbench/reference/axk1.py``). ``checks.LOGITS_RTOL`` (0.03, set on a dense
model) is not loosened: a model with routed experts brings its own limits, with
their reasons. Two numbers, and a run is correct within both.

**The block's relative L2 error.** Why this model reads higher than a dense one:
the router is float32 in both, but it scores hidden states that the system
carries in bf16. Where a held expert's score lies closer to the cut between
chosen and left out than that rounding moves it, the two sides choose
differently for that token, in that layer, and the token's row of logits then
differs by an expert's whole contribution and not by a rounding (a row reads
0.015 without such a flip and 0.07 to 0.25 with one; 6 to 13% of the rows of a
sound run have one). Only choices among the 12 experts held here show (a flip
between two absent experts changes the weights' normaliser a little, no more).
So the block's error is a floor of bf16 rounding through 7 layers plus a few
tokens' flips. It catches what moves every row: the reference with float8
matmul inputs reads 0.61 to 0.76.

**The share of settled rows that are off.** The block's error cannot tell one
held expert skipped from a few more flips: with one of the 12 skipped in every
layer (it gets 0.2 to 15% of a layer's tokens, by the skew) the block read 0.047
to 0.076 on the chip, under any limit that lets the sound runs pass. So the
reference also says by how much of a score each position's choice was made as
far as the held experts go (``reference.forward_and_margin``), and the rows
whose least margin over the expert layers is above ``MLA_MOE_SETTLED_MARGIN``
are *settled*: no rounding of the system's flips them. Of those, the share
whose own relative L2 error is above ``MLA_MOE_ROW_RTOL`` is the second number.

The readings that place the limits (my chip runs, PR 27; PERF.md section 6 has
the seeds):

* the system, one run a seed: the block 0.027 to 0.046 over 72 runs of 66
  seeds; settled rows off 0 to 0.97% over 15 seeds (0 or 1 row of 103 to 131
  settled among 256; 0 to 0.67% among the last 1024 positions, 8 seeds);
* the reference itself with both operands of every matmul rounded to
  ``float8_e4m3fn``, the nearest precision below the bf16 the configuration
  states (``reference.forward(..., matmul_inputs=...)``), put through
  ``compare_logits`` in the system's place: the block 0.61 to 0.76 over 11
  seeds, every settled row off: not correct. With bf16 inputs it reads as the
  system does (0.017 to 0.034, settled rows off 0 to 0.8%);
* the system with one held expert's down projection zeroed on the chip, 8
  seeds: in every expert layer 8.3 to 28% of the settled rows off (the block
  0.050 to 0.074), in one layer 2.3 to 19%.

A missing term is no rounding: each mutation the CPU tests make (no shared
expert, no 2.5, no group limit, softmax for sigmoid, ``m**2`` dropped from the
scale, rope on ``k_nope``, one held expert skipped, a bf16 router) fails the
comparison with every expert held, and all but the last with a share held: a
bf16 router makes flips, which is what the settled rows leave out, and at a
share few of them show.
"""

from __future__ import annotations

import numpy as np

from perfbench import checks

# The block: 1.7 times the largest sound reading and a ninth of the float8 one;
# fresh seeds read a little higher, a lower precision reads ten times higher.
MLA_MOE_LOGITS_RTOL = 0.08
# A position is settled where the reference's routers chose by more than this
# much of a sigmoid score in every expert layer: 41 to 51% of the positions.
# The margin of a row that was off in a sound run was 0.0008 at the median,
# 0.0033 at the 95th percentile and 0.0126 at most (763 such rows of 8192).
MLA_MOE_SETTLED_MARGIN = 0.005
# A settled row without a flip reads 0.014 to 0.017, one that lost or gained an
# expert 0.07 and more.
MLA_MOE_ROW_RTOL = 0.04
# Five times the largest sound reading, six tenths of the least with an expert
# skipped in every layer.
MLA_MOE_ROWS_OVER = 0.05


def row_errors(system_logits, reference_logits) -> np.ndarray:
    """Each compared position's own relative L2 error: its row of logits, the
    difference's norm over the reference row's (a row's norm counts as at least
    a tenth of the block's root-mean-square row norm)."""
    got = np.asarray(system_logits, np.float64).reshape(-1, np.shape(reference_logits)[-1])
    want = np.asarray(reference_logits, np.float64).reshape(got.shape)
    norms = np.linalg.norm(want, axis=1)
    return np.linalg.norm(got - want, axis=1) / np.maximum(norms, max(0.1 * np.sqrt(np.mean(norms ** 2)), 1e-30))


def compare_logits(system_logits, reference_logits, reference_margin) -> dict:
    """``reference_margin``: for each compared position, the least margin by
    which a router of the reference chose (``reference.forward_and_margin``)."""
    err = checks.relative_l2(system_logits, reference_logits)
    settled = np.asarray(reference_margin).reshape(-1) > MLA_MOE_SETTLED_MARGIN
    rows = row_errors(system_logits, reference_logits)[settled]
    rows_over = float(np.mean(rows > MLA_MOE_ROW_RTOL)) if rows.size else 0.0
    finite = bool(np.isfinite(np.asarray(system_logits, np.float32)).all())
    return {"ok": bool(finite and err <= MLA_MOE_LOGITS_RTOL and rows_over <= MLA_MOE_ROWS_OVER),
            "logits_rel_l2": err, "logits_rtol": MLA_MOE_LOGITS_RTOL,
            "settled_rows": int(settled.sum()), "settled_rows_over": rows_over,
            "settled_rows_over_limit": MLA_MOE_ROWS_OVER, "row_rtol": MLA_MOE_ROW_RTOL,
            "compared": list(np.shape(reference_logits))}
