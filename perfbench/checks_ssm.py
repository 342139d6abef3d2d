"""The limit of the comparison that decides ``correct`` for the ``forward_ssm``
job: logits of the last ``LOGIT_POSITIONS`` positions of the one checked
sequence, what the timed program gave for it at the timed sizes (bf16 weights and
activations; float32 accumulation, decays, sums and norms) against the float32
reference (``perfbench/reference/granite_hybrid.py``, whose recurrence is a scan
over positions). One number, the block's relative L2 error; a run is correct
within it. Nothing in this model chooses (no router, no selection), so no row is
set apart and no margin is kept.

**What the comparison has to hear.** With every leaf at N(0, 0.02) a Mamba-2
layer has ``dt = softplus(0) = 0.69`` and ``A = -1`` in every head, so that a
state halves each step; its convolution's output is a fiftieth of its input, so
that B and C are 0.02 and the state's term 5e-4 of the ``D x`` beside it; and under
``attention_multiplier`` = 1/64 the attention layers average all earlier values
and add 1/200 of what a Mamba-2 layer adds. A program that dropped the
recurrence, or the attention layers, whole would read as rounding. The job
therefore draws the state-space leaves as Mamba-2 initialises them, B and C four
times and q and k eight times larger (``forward_ssm.with_ssm_draw``; the
configuration file's ``assumed``), and the readings below are with that draw.

The readings that place the limit (my chip runs, PR 44, at the timed sizes, the
last 1024 positions; PERF.md section 6 has the seeds):

* the system: 0.0546 to 0.0582 over 12 seeds, its worst row 0.065 to 0.074, its
  median row 0.054 to 0.058: twice the reference's own reading at bf16, because
  the system also rounds what lies between its matmuls (the residual stream, the
  decomposition's ``dt x``, the within-chunk factors, the chunks' summaries and
  the entering states) where the reference at bf16 rounds the matmuls' inputs;
* the reference itself with both operands of every matmul, and of the
  recurrence's two products, rounded to ``float8_e4m3fn``, the nearest precision
  below the bf16 the configuration states, put through ``compare_logits`` in the
  system's place: **0.448**, every row off (median 0.447): not correct. With bf16
  inputs 0.0281;
* the system mutated on the chip (one reference forward, seed 440000201, where
  the unmutated system reads 0.0558): the state not carried between chunks
  **0.971**; the decay made a constant a head (its mean step) **0.917**; every
  row off in both.

A missing term is no rounding: each mutation the CPU tests make at the stand-in's
sizes in float32 (the state not carried between chunks, the decay a constant a
head, ``D`` dropped, the convolution dropped, its bias dropped, the gate dropped,
``softplus`` dropped, the softmax scale at sdpa's default, the residual multiplier
dropped) fails the comparison, the least of them at 0.15 (the decay a constant a
head; the convolution's bias dropped), where the unmutated system reads 1e-6.
"""

from __future__ import annotations

import numpy as np

from perfbench import checks
from perfbench.checks_mla_moe import row_errors

# The head runs on the last 1024 positions and all of them are compared.
LOGIT_POSITIONS = 1024
# 1.72 times the largest sound reading; between a quarter and a fifth of the reference's at float8; a ninth of the
# least mutation's on the chip (0.917), two thirds of the least at the stand-in's sizes (0.15).
SSM_LOGITS_RTOL = 0.1


def compare_logits(system_logits, reference_logits) -> dict:
    err = checks.relative_l2(system_logits, reference_logits)
    rows = row_errors(system_logits, reference_logits)
    finite = bool(np.isfinite(np.asarray(system_logits, np.float32)).all())
    return {"ok": bool(finite and err <= SSM_LOGITS_RTOL),
            "logits_rel_l2": err, "logits_rtol": SSM_LOGITS_RTOL,
            "row_median": float(np.median(rows)), "row_max": float(rows.max()),
            "compared": list(np.shape(reference_logits))}
