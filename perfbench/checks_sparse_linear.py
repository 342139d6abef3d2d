"""The limit of the comparison that decides ``correct`` for the
``forward_sparse_linear`` job: logits of the last ``LOGIT_POSITIONS``
positions of the one checked sequence, what the timed program gave for it at
the timed sizes (bf16 weights and activations, float32 accumulation, softmax
and selection) against the float32 reference
(``perfbench/reference/minicpm_sala.py``). One number, the block's relative L2
error; a run is correct within it.

**Why one number is enough here, where the expert models needed two.** The
sparse layers choose, as routers do, and the system and the reference choose
otherwise wherever two blocks' scores lie closer than bf16 hidden states move
them. They do so all the time: adjacent blocks share a pooled key (a block's
score is a max over the 5 that overlap it, and the 5th is the next block's
1st), so the 64th and the 65th best score are *equal* for more than half of
the compared positions in some layer and key-value head, and closer than 3e-6
for nine in ten (the reference's own count on the chip, seed 2147483721). But
a flipped block is one of 64, of 64 keys among 4,096 that random weights attend
to almost evenly: it moves a row by less than the rounding does. The reference
with bf16 matmul inputs, which flips nothing the system's hidden states would,
reads 0.024 where the system reads 0.026 to 0.033. So no margin is kept and no
row is set apart.

**What the comparison has to hear.** With random weights attention is diffuse,
and a sparse layer's output, a mean of some 4,096 values, is so near zero that
at the weights' N(0, 0.02) its output projection adds a hundredth of what a
linear layer (whose output is normed) or an MLP adds: dropping the layer whole
would read as rounding. The job therefore draws the sparse layers' output
projection four times larger (``forward_sparse_linear.SPARSE_OUT_SCALE``; the
configuration file's ``assumed``), and the readings below are with it.

The readings that place the limit (my chip runs, PR 33, at the timed sizes, the
last 1024 positions; PERF.md section 6 has the seeds):

* the system: 0.0264 to 0.0331 over 10 runs of 9 seeds, its worst row 0.058 to
  0.069, its median row 0.022 to 0.025;
* the reference itself with both operands of every matmul rounded to
  ``float8_e4m3fn``, the nearest precision below the bf16 the configuration
  states, put through ``compare_logits`` in the system's place: **1.08**, every
  row off (median 1.08): not correct. With bf16 inputs 0.0242;
* the system mutated on the chip (seed 2147483721, one reference forward):
  only the forced blocks attended **0.326**; dense attention in place of step 6
  **0.299**; the decay dropped 0.895; the sparse layers' gate dropped 1.04;
  the linear layers' gate dropped 0.691.

A missing term is no rounding: each mutation the CPU tests make at the
stand-in's sizes in float32 (those five, step 4's pooling dropped, the output
norm dropped, the residual scale dropped) fails the comparison, the least of
them at 0.085 (dense attention in place of step 6), where the unmutated system
reads 6e-7.
"""

from __future__ import annotations

import numpy as np

from perfbench import checks
from perfbench.checks_mla_moe import row_errors

# The head runs on the last 1024 positions and all of them are compared.
LOGIT_POSITIONS = 1024
# 1.8 times the largest sound reading; a fifth of the least mutation's on the chip (0.299), seven tenths of the least at
# the stand-in's sizes (0.085), an eighteenth of the reference's at float8.
SPARSE_LINEAR_LOGITS_RTOL = 0.06


def compare_logits(system_logits, reference_logits) -> dict:
    err = checks.relative_l2(system_logits, reference_logits)
    rows = row_errors(system_logits, reference_logits)
    finite = bool(np.isfinite(np.asarray(system_logits, np.float32)).all())
    return {"ok": bool(finite and err <= SPARSE_LINEAR_LOGITS_RTOL),
            "logits_rel_l2": err, "logits_rtol": SPARSE_LINEAR_LOGITS_RTOL,
            "row_median": float(np.median(rows)), "row_max": float(rows.max()),
            "compared": list(np.shape(reference_logits))}
