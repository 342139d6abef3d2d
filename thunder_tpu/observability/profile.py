"""Profiler bracketing: ``thunder_tpu.profile(fn, *args)``.

Runs a (compiled or plain) callable under ``jax.profiler.trace`` with one
``StepTraceAnnotation`` per step, producing an xprof-ready trace directory.
Combined with annotated codegen
(``THUNDER_TPU_ANNOTATE_TRACES=1``; see ``core/trace.py``), every HLO row in
the profile carries the originating trace line + pass provenance, so
profiler time attributes back to BoundSymbols.

On backends without a profiler plugin the bracket degrades to wall-clock
timing (``trace_dir`` comes back None) instead of failing the run.
"""

from __future__ import annotations

import os
import time
from typing import Any, Callable, Optional

from thunder_tpu.observability.events import emit_event


def _count_capture(*, ok: bool) -> None:
    """Bump ``thunder_tpu_profile_captures_total{ok=}`` past the metrics
    gate (always-export; never fails the bracket)."""
    try:
        from thunder_tpu.observability import metrics as obsm

        obsm.PROFILE_CAPTURES.inc_always(ok="true" if ok else "false")
    except Exception:
        pass


def _block_on(out: Any) -> None:
    """Synchronize on every array leaf so the profiled region contains the
    device work, not just its async dispatch."""
    from thunder_tpu.core.pytree import tree_flatten

    flat, _ = tree_flatten(out)
    for x in flat:
        if hasattr(x, "block_until_ready"):
            x.block_until_ready()


def profile(
    fn: Callable,
    *args,
    trace_dir: Optional[str] = None,
    steps: int = 3,
    warmup: int = 1,
    step_name: str = "thunder_step",
    **kwargs,
) -> dict:
    """Bracket ``steps`` calls of ``fn(*args, **kwargs)`` with jax profiler
    markers and write an xprof-ready trace directory.

    Returns ``{"trace_dir", "steps", "avg_s", "total_s", "profiler",
    "attribution"}`` — ``profiler`` is False when the backend has no
    profiler plugin and only wall-clock numbers were collected.

    ``attribution`` closes the loop in-process: when the profiler ran and
    the trace-events carry annotated-codegen scopes (run under
    ``THUNDER_TPU_ANNOTATE_TRACES=1``), it is an
    :class:`~thunder_tpu.observability.attribution.Attribution` mapping
    measured device time back to trace lines (None otherwise). Join it with
    the static cost model via ``thunder_tpu.monitor.attribution_report`` or
    ``scripts/perf_report.py --trace-dir``; see docs/performance.md.
    """
    import jax

    if trace_dir is None:
        import tempfile

        trace_dir = tempfile.mkdtemp(prefix="thunder_tpu_prof_")
    else:
        os.makedirs(trace_dir, exist_ok=True)

    for _ in range(max(0, warmup)):
        _block_on(fn(*args, **kwargs))

    emit_event("profile_start", dir=trace_dir, steps=steps)
    # Only profiler SETUP failures degrade to wall-clock; an exception from
    # the profiled fn itself (a NaNWatchError, a consumed donated buffer)
    # must propagate — re-running the loop would misdiagnose it as a missing
    # profiler plugin and double-consume donated inputs.
    profiler_ctx = None
    profiler_ok = False
    try:
        profiler_ctx = jax.profiler.trace(trace_dir)
        profiler_ctx.__enter__()
        profiler_ok = True
    except Exception as e:  # profiler plugin unavailable: degrade, don't fail
        profiler_ctx = None
        import warnings

        warnings.warn(
            f"jax profiler unavailable ({type(e).__name__}: {e}); "
            "collecting wall-clock only",
            stacklevel=2,
        )
        # A degraded capture must be loud beyond the one-shot warning: the
        # roofline duty cycle (ISSUE 19) calls this bracket unattended, and
        # a plugin-less backend would silently produce wall-clock-only
        # probes forever. The always-export counter reaches /metrics and
        # degrades the /healthz `profile` component; the typed event lands
        # in the log/flight recorder next to the probes it explains.
        _count_capture(ok=False)
        emit_event(
            "profile_degraded", reason=f"{type(e).__name__}: {e}")

    out = None
    t0 = time.perf_counter()
    try:
        for i in range(steps):
            if profiler_ok:
                with jax.profiler.StepTraceAnnotation(step_name, step_num=i):
                    out = fn(*args, **kwargs)
            else:
                out = fn(*args, **kwargs)
        _block_on(out)
    finally:
        if profiler_ctx is not None:
            profiler_ctx.__exit__(None, None, None)
    total = time.perf_counter() - t0
    if profiler_ok:
        _count_capture(ok=True)
    result = {
        "trace_dir": trace_dir if profiler_ok else None,
        "steps": steps,
        "total_s": total,
        "avg_s": total / max(1, steps),
        "profiler": profiler_ok,
    }
    emit_event("profile_stop", **result)
    # Best-effort in-process attribution (never fails the profile): only
    # meaningful when annotated codegen stamped scopes into HLO metadata.
    result["attribution"] = None
    if profiler_ok:
        try:
            from thunder_tpu.observability.attribution import attribute

            attr = attribute(trace_dir)
            if attr.by_line:
                result["attribution"] = attr
        except Exception:
            pass
    return result
