"""The one place that says which passes stand between an acquired trace and a
claimed one, and in which order.

Every front end (``api.jit`` and its ``vmap``/``jvp`` re-staging,
``parallel.build_train_step``, the module front end, the collective and
pipeline-parallel stagers, ``examine.lint`` and the two static reports) makes
the same two calls, with work of its own between them where it has any::

    acquired  --clean-->  clean  --compile_trace-->  claimed

The order is the four tuples below, read top to bottom. A new pass is one line
in one of them; a pass that does not apply to a trace declines it by its own
rule (a fold that needs a backward returns a forward as it came), so no caller
chooses among them. This module imports ``transforms/`` and
``executors/passes.py``; nothing in those imports it.
"""

from __future__ import annotations

import time
from typing import Callable, NamedTuple, Sequence

from thunder_tpu.core.trace import TraceCtx
from thunder_tpu.executors.passes import transform_for_execution
from thunder_tpu.transforms.attention_layout import FOLDED_TAG as LAYOUTS_FOLDED_TAG
from thunder_tpu.transforms.attention_layout import fold_attention_layouts
from thunder_tpu.transforms.attention_residuals import save_sdpa_residuals_joint
from thunder_tpu.transforms.common import cse, dce
from thunder_tpu.transforms.cross_entropy_upcast import FOLDED_TAG, fold_cross_entropy_upcasts
from thunder_tpu.transforms.rng import functionalize_rng_ops
from thunder_tpu.transforms.ssm_layout import FOLDED_TAG as SSM_LAYOUTS_FOLDED_TAG
from thunder_tpu.transforms.ssm_layout import fold_ssm_layouts

# trace -> trace: what every front end does to an acquired trace
CLEAN = (dce, cse)
# (the caller's trace transforms run here: grad, autocast)
# (trace, executors) -> trace: each rewrites for a kernel an executor of the
# list would claim; the de-opt ladder's level 1 ("no fusion") leaves them out
REWRITES = (save_sdpa_residuals_joint, fold_cross_entropy_upcasts, fold_attention_layouts, fold_ssm_layouts)
# trace -> trace: always
LOWER = (functionalize_rng_ops,)
# (trace, executors) -> trace: the claim
CLAIM = transform_for_execution

# What the ``transforms`` phase record carries beside its seconds, by presence:
# a compile that ran no rewrite carries none.
_COUNTED = (FOLDED_TAG, LAYOUTS_FOLDED_TAG, SSM_LAYOUTS_FOLDED_TAG)


class Compiled(NamedTuple):
    claimed: TraceCtx  # every bound symbol has its executor
    traces: tuple  # each trace a stage made, in order; ``claimed`` is the last
    seconds: dict  # {"transforms": s, "claim": s}: the two compile phases of this call
    extras: dict  # {"transforms": {tag: count}}: the extras of those phases' records


def clean(trace: TraceCtx) -> tuple:
    """The acquired ``trace`` through ``CLEAN``: one trace a pass, the last is
    the clean one."""
    out = []
    for step in CLEAN:
        out.append(trace := step(trace))
    return tuple(out)


def compile_trace(trace: TraceCtx, executors: Sequence, *, transforms: Sequence[Callable] = (),
                  rewrites: bool = True) -> Compiled:
    """A clean ``trace`` through the caller's ``transforms``, ``REWRITES``
    (not where ``rewrites`` is false), ``LOWER`` and ``CLAIM``. A stage that
    hands back the trace it was given (it declined, or rewrote in place) adds
    nothing to ``traces``."""
    start = time.perf_counter()
    steps = [*transforms]
    if rewrites:
        steps += [lambda t, step=step: step(t, executors) for step in REWRITES]
    steps += LOWER
    made = []
    for step in steps:
        new = step(trace)
        if new is not trace:
            made.append(trace := new)
    counted = {tag: trace.tags[tag] for tag in _COUNTED if tag in trace.tags}
    claim_start = time.perf_counter()
    claimed = CLAIM(trace, executors)
    end = time.perf_counter()
    return Compiled(claimed, (*made, claimed),
                    {"transforms": claim_start - start, "claim": end - claim_start},
                    {"transforms": counted})
