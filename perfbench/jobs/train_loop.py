"""Job ``train_loop``: job ``train`` (training steps through
``parallel.build_train_step``, a fresh seeded batch a step, no loss read inside
the window) for a looped model, whose layers run ``total_ut_steps`` times a step
with the same weights and whose loss is the exit objective over every loop
step's head (``models/gpt.py::_exit_loss``). What differs from ``train``: the
required operations count every loop step's layers and head
(``perfbench/flops_loop.py``); the exit heads' least work and the regions of the
compiled program are handed to the readers; the executable is kept, so that the
regions can be read after the state is released; and the check, ``train``'s on
one chip against ``perfbench/reference/ouro.py``, judges the exit gate's bias by
a rule of its own (``perfbench/checks_loop.py``) and can print the reference at
lower precisions beside it."""

from __future__ import annotations

import gc
import os
import time
import types

import numpy as np

from perfbench import checks, checks_loop, flops_loop, weights
from perfbench.jobs import forward_window_moe, train
from perfbench.layer_metrics import _regions

REGIONS = ("exit.heads", "exit.gate")


class Job(train.Job):
    def __init__(self, cell, **how):
        super().__init__(cell, **how)
        self._compiled = None
        self.counters["mixer_work"] = {"exit_heads": flops_loop.exit_heads(
            self.tokens_per_unit, self.keys["hidden_size"], self.keys["vocab_size"], self.cfg.loop_steps)}
        self.counters["region_of_instruction"] = lambda: _regions.of_instructions(
            forward_window_moe.an_instruction_a_line(self.compiled().as_text()), REGIONS)

    def flops_per_token(self) -> float:
        return 3.0 * flops_loop.forward_flops_per_token(self.keys, self.seq)

    def compiled(self):
        if self._compiled is None:
            self._compiled = super().compiled()
        return self._compiled

    def check(self, reference) -> dict:
        """``train.Job.check`` on one chip under this job's comparison
        (``perfbench/checks_loop.py``): one step from the seeded weights and
        zero moments on the first batch against the reference's loss and
        sampled gradient. ``PERFBENCH_CHECK_PRECISIONS`` (dtype names,
        comma-separated; unset in the driver's runs) is the builder's reading of
        what the comparison hears: the reference itself with its matmul inputs
        rounded to each goes through the same comparison in the system's place,
        and its verdict is printed under ``reference_at``. It never changes ``ok``."""
        import jax.numpy as jnp

        from thunder_tpu.parallel import adamw_init

        clock = [time.perf_counter()]

        def lap() -> float:
            clock.append(time.perf_counter())
            return round(clock[-1] - clock[-2], 2)

        idx, tgt = self.first_batch
        plan = checks.sample_plan(weights.leaf_kinds(self.shapes), self.cfg.n_layer, idx, self.seed)
        params = self._weights()
        params, opt, loss = self.step(params, adamw_init(params), idx, tgt)
        system = float(np.asarray(loss)), checks.system_gradient_sample(opt["m"], plan, self.traffic["b1"])
        del params, opt, loss
        gc.collect()
        seconds = {"system_step_and_sample": lap()}

        stacked = weights.make_reference_weights(self.shapes, self.seed)
        idx, tgt = jnp.asarray(idx), jnp.asarray(tgt)
        exact = checks.reference_loss_and_gradient_sample(reference, stacked, plan, idx, tgt, self.keys)
        seconds["reference_loss_and_gradient"] = lap()
        verdict = checks_loop.compare_training(*system, *exact)
        for dtype in filter(None, os.environ.get("PERFBENCH_CHECK_PRECISIONS", "").split(",")):
            at = types.SimpleNamespace(loss=lambda *a, d=dtype: reference.loss(*a, matmul_inputs=d))
            got = checks_loop.compare_training(*checks.reference_loss_and_gradient_sample(
                at, stacked, plan, idx, tgt, self.keys), *exact)
            verdict.setdefault("reference_at", {})[dtype] = {k: got[k] for k in ("ok", "loss_rel_err", "grad_worst")}
        verdict["seconds"] = seconds
        if self.step._cache_size() != 1:  # the check's own step must have hit the compiled one
            verdict.update(ok=False, traced=self.step._cache_size())
        return verdict


lower_for = train.lower_for
