"""The comparison that decides ``correct`` for the ``train_loop`` job:
``checks.compare_training`` (the loss of one step and a gradient sample of
every kind of leaf, under its limits and for its reasons), with one rule of its
own, for the exit gate's bias.

The bias is one number, and its gradient is the sum over the 4,096 tokens of
each token's ``dL/dz``: one more column of the gate's Linear, whose input is 1
where the weight's are the normed state. On the chip (my runs, PR 47, at the
cell's sizes) its relative error read 0.0003 to 0.018 on ten seeds and **0.187
on an eleventh** (470000105), where every other leaf of every seed read under
0.11: there the bias's gradient was 0.0010 and its error 0.00019, while the
gate's row ``[dw | db]`` had a root-mean-square entry of 0.0068 and the weight's
part of it read 0.025, the same absolute error an entry. A row's relative L2
averages its entries' errors and ``checks.worst_row`` floors a row at a tenth of
the typical row; one number has nothing to average, and its relative error
grows without bound as its value falls among its row's. So the bias counts as
at least its row's root-mean-square entry: its error is over the larger of its
own gradient and that (0.028 at 470000105, 0.0014 to 0.013 on the other seeds
of the last call). A bias the system dropped, whose gradient would then read 0,
reads its reference's gradient over that floor, 1 where it is a typical entry of
its row or more: 0.90, 1 and 1 on three of those four seeds, 0.15 at 470000105.
"""

from __future__ import annotations

import numpy as np

from perfbench import checks

GATE_BIAS, GATE_WEIGHT = "exit_gate_b@None", "exit_gate_w@None"
BIAS_FLOOR = 1.0  # of the root-mean-square entry of the gate's gradient row: one number has nothing to average


def gate_bias_error(system_sample: dict, reference_sample: dict) -> tuple[float, float]:
    """(the bias's error, the floor its reference gradient counts as at least)."""
    ref, got = (float(np.ravel(s[GATE_BIAS])[0]) for s in (reference_sample, system_sample))
    row = np.append(np.ravel(np.asarray(reference_sample[GATE_WEIGHT], np.float64)), ref)
    floor = BIAS_FLOOR * float(np.sqrt(np.mean(row ** 2)))
    return abs(got - ref) / max(abs(ref), floor, 1e-30), floor


def compare_training(system_loss: float, system_sample: dict, reference_loss: float,
                     reference_sample: dict) -> dict:
    loss_err = abs(system_loss - reference_loss) / abs(reference_loss)
    errs = {k: checks.sample_error(system_sample[k], reference_sample[k]) for k in reference_sample}
    errs[GATE_BIAS], floor = gate_bias_error(system_sample, reference_sample)
    worst = max(errs, key=errs.get)
    return {
        "ok": bool(loss_err <= checks.LOSS_RTOL and errs[worst] <= checks.GRAD_RTOL
                   and all(np.isfinite(v).all() for v in system_sample.values())),
        "loss": [system_loss, reference_loss], "loss_rel_err": loss_err, "loss_rtol": checks.LOSS_RTOL,
        "grad_worst": [worst, errs[worst]], "grad_rtol": checks.GRAD_RTOL,
        "gate_bias": {"system": float(np.ravel(system_sample[GATE_BIAS])[0]),
                      "reference": float(np.ravel(reference_sample[GATE_BIAS])[0]), "floor": floor},
        "grad_err": {k: float(f"{v:.3e}") for k, v in sorted(errs.items())},
    }
