"""The limits of the comparison that decides ``correct`` for the
``forward_scmoe`` job: logits of the last ``LOGIT_POSITIONS`` positions of the
one checked sequence, what the timed program gave for it at the timed sizes
(bf16 weights and activations, float32 accumulation, softmax and router) against
the float32 reference (``perfbench/reference/longcat_flash.py``). Two numbers
over the rows whose routing is settled, as ``perfbench/checks_window_moe.py`` has
them and for its reasons, re-derived at this model; a run is correct within both.

**What a row looks like here.** The router is a softmax over 768 outputs: the
chosen scores lie near 0.01 to 0.04 and the twelfth and thirteenth a few
ten-thousandths apart, where the bf16 the system carries its hidden states in
moves a score by up to a thousandth. So nearly every row has a choice that
differs from the reference's in some layer, and most do not matter: the weights
are **not normalised**, so a choice that differs among the 496 experts held
elsewhere moves nothing here; one that takes or leaves a zero-compute output
moves the row by ``6 p`` of the router's input, a hundredth of the residual
stream at the cell's size, under the bf16 rounding of a row (0.031 at the
median, 0.023 for the reference with bf16 matmul inputs); one that takes or
leaves one of the 16 held experts moves it by that expert's whole term, which
is loud because the held experts' down projections are drawn four times larger
(the configuration file's ``assumed``; without that an expert lost is a
hundredth of the stream and no comparison of logits hears it): such rows read
0.05 to 0.17, 30 to 42 of a run's 1,024.

**Settled rows.** The reference says by how much of a biased score each
position's choice was made, as far as the held experts go and as far as the
zero-compute outputs go (``reference.forward_and_margin``). A row is settled
where the first is above ``SCMOE_SETTLED_MARGIN`` and the second above
``SCMOE_ZERO_SETTLED_MARGIN`` in every layer: 328 to 378 of 1,024. The second
margin is small because such a flip is quiet at the cell's size; it is there
because at the stand-in's size (``--rehearse``: 128 wide) the zero-compute term
is most of the residual stream and one flip is the whole row. **The block's
relative L2 error** is taken over the settled rows; **the share of settled rows
that are off** (their own relative L2 error above ``SCMOE_ROW_RTOL``) is the
second number.

The readings that place the limits (my chip runs, PR 40, at the timed sizes, the
last 1,024 positions, at the limits that stand; PERF.md section 6 has the seeds):

* the system, 8 seeds: the block 0.0305 to 0.0315 (over every row 0.0339 to
  0.0365); settled rows off 0 in five runs and 1 of 350 to 370 in three (0.29%
  at most: 0.0529 to 0.0555); the largest settled row otherwise 0.039 to 0.047;
* the reference itself with both operands of every matmul rounded to
  ``float8_e4m3fn``, the nearest precision below the bf16 the configuration
  states, put through ``compare_logits`` in the system's place: the block **0.945
  to 0.976, every row off**: not correct, by both limits. With bf16 inputs 0.0228
  and 0.0232 and no settled row off (the largest 0.028);
* the system mutated on the chip against one reference forward (seed 4000000201,
  332 settled rows, the sound system 0.0308 with none off): the zero-compute term
  left out **0.264**; the routed layer reading the second sublayer's normed input
  0.300, or joining the residual before the second sublayer 0.329; q's latent
  scale left out 1.15, the kv latent's 1.29; the weights renormalised over the
  12 chosen 0.536; the softmax taken over the 512 real experts only 0.277: every
  settled row off in each, not correct by both limits;
* **one held expert's down projection zeroed in one layer**: the expert most of
  the sequence's rows went to in the second routed layer (714 of 16,384) reads the
  block 0.0507 and **16 of 332 settled rows off (4.8%)**, the largest 0.46: not
  correct by both limits; the busiest of the fourth layer (502 rows) 4 of 332
  (1.2%): not correct by the second limit, by one row; an expert of median load
  in the third layer (196 rows, 1.2% of the sequence) 2 of 332 (0.6%): correct.

**The smallest fault the second limit sees, said plainly.** A term lost in one
layer is seen where more than ``SCMOE_ROWS_OVER`` of the settled rows met it:
four rows of some 350, so an expert that gets some 3% of a layer's rows or more.
A settled row that met a zeroed expert read 0.06 to 0.46. An expert of the even
load (256 rows, 1.6%) lost in one layer alone is not heard; lost in every layer,
or with its neighbours, it is. And a bias added into the weights and not only
into the choice (no mutation of ISSUE 40's) is not heard at all at this size of
bias: N(0, 0.0015) moves a weight ``6 p`` by 0.009 of 0.06 to 0.24 (the block
0.0331, 2 of 332 rows off); the CPU test hears it at the stand-in's N(0, 0.005).

A missing term is no rounding: each mutation the CPU tests make at the
stand-in's sizes in float32 (``tests/test_longcat_flash.py``: those above and the
bias weighed) fails the comparison, where the unmutated system reads 1e-6.
"""

from __future__ import annotations

import numpy as np

from perfbench import checks
from perfbench.checks_mla_moe import row_errors

# The head runs on the last 1024 positions and all of them are compared.
LOGIT_POSITIONS = 1024
# The block over the settled rows: 1.6 times the largest sound reading (0.0315 over 8 seeds, which lie within 0.001 of each
# other) and a twentieth of the float8 one.
SCMOE_LOGITS_RTOL = 0.05
# A settled row reads 0.031 at the median and 0.047 at most where no held expert flipped; one that lost or gained a held
# expert's term 0.053 and more.
SCMOE_ROW_RTOL = 0.05
# Of 328 to 378 settled rows a sound run has none or one off (0.29% at most) and three still pass; one held expert zeroed
# in one layer, a scale or the zero-compute term left out read far above (the docstring has each).
SCMOE_ROWS_OVER = 0.01
# A position is settled where the reference's routers chose, in every layer, by more than this much of a biased softmax
# score as far as the 16 held experts go (at 0.0005 a run has 6 to 11 rows off among some 880, at 0.001 none to 3 among some
# 750, at 0.002 none or one among some 540) ...
SCMOE_SETTLED_MARGIN = 0.001
# ... and by more than this much as far as the 256 zero-compute outputs go: quiet at the cell's size (with it 0 or 1 row
# off of some 350, without it 0 to 3 of some 750), the whole row at the stand-in's.
SCMOE_ZERO_SETTLED_MARGIN = 0.0002
NEIGHBOURING_MARGINS = (0.0003, 0.0005, 0.001, 0.0015, 0.002)  # printed beside the limit's own, for whoever re-derives it
NEIGHBOURING_ZERO_MARGINS = (0.0, 0.0001, 0.0002, 0.0003, 0.0005)


def compare_logits(system_logits, reference_logits, reference_margin) -> dict:
    """``reference_margin``: for each compared position, the least margins by
    which a router of the reference chose (``reference.forward_and_margin``): as
    far as the held experts go, and as far as the zero-compute outputs go."""
    every = row_errors(system_logits, reference_logits)
    margin, zero_margin = np.asarray(reference_margin).reshape(-1, 2).T
    settled = (margin > SCMOE_SETTLED_MARGIN) & (zero_margin > SCMOE_ZERO_SETTLED_MARGIN)
    rows = every[settled]
    flat = lambda logits: np.asarray(logits, np.float32).reshape(-1, np.shape(reference_logits)[-1])[settled]
    err = checks.relative_l2(flat(system_logits), flat(reference_logits)) if rows.size else float("inf")
    rows_over = float(np.mean(rows > SCMOE_ROW_RTOL)) if rows.size else 0.0
    finite = bool(np.isfinite(np.asarray(system_logits, np.float32)).all())
    off = every > SCMOE_ROW_RTOL
    return {"ok": bool(finite and err <= SCMOE_LOGITS_RTOL and rows_over <= SCMOE_ROWS_OVER),
            "logits_rel_l2": err, "logits_rtol": SCMOE_LOGITS_RTOL,
            "every_rows_rel_l2": checks.relative_l2(system_logits, reference_logits),
            "settled_rows": int(settled.sum()), "settled_rows_over": rows_over, "settled_rows_over_limit": SCMOE_ROWS_OVER,
            "row_rtol": SCMOE_ROW_RTOL, "settled_margins": [SCMOE_SETTLED_MARGIN, SCMOE_ZERO_SETTLED_MARGIN],
            # what the limits were placed by: every row's error, the settled rows', the largest margin of a row that is
            # off, and the rows (all, off) that neighbouring margins would call settled
            "row_median": float(np.median(every)), "row_p99": float(np.quantile(every, 0.99)), "row_max": float(every.max()),
            "settled_row_median": float(np.median(rows)) if rows.size else None,
            "settled_row_max": float(rows.max()) if rows.size else None,
            "rows_above": {str(t): [int((every > t).sum()), int((rows > t).sum())] for t in (0.03, 0.04, 0.05, 0.06, 0.075, 0.1)},
            "at_margins": {f"{m}/{z}": [int(s.sum()), int(off[s].sum()), float(every[s].max()) if s.any() else None]
                           for m in NEIGHBOURING_MARGINS for z in NEIGHBOURING_ZERO_MARGINS
                           for s in [(margin > m) & (zero_margin > z)]},
            "compared": list(np.shape(reference_logits))}
