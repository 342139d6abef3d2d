"""Compile de-optimization ladder + the recovery driver the dispatcher uses.

On a compile failure or device OOM the runtime does not die — it walks a
staged de-opt ladder, recompiling with progressively safer (slower,
smaller-memory) configurations, with bounded retries and exponential
backoff:

====  ==========================================================
L0    normal compilation
L1    disable fusion passes (the rewrites of ``thunder_tpu/pipeline.py``)
      and XLA buffer donation
L2    L1 + aggressive rematerialization (transforms/rematerialization
      recomputes longer chains regardless of saved-byte accounting)
L3    L2 + exact shapes (no bucket padding; shrinks live memory for
      symbolic-values entries)
====  ==========================================================

The per-function ladder position is sticky on ``CompileData`` (a function
that OOMs at L0 compiles at L1 from then on; the TTL story for climbing
back up is future work) and each entry records the level it was compiled
at — surfaced as ``degradation_level`` in ``thunder_tpu.cache_info``.

On an **OOM**-shaped failure the ladder no longer climbs blind: the static
liveness planner (``analysis/liveness.py``, ISSUE 10) prices the peak HBM
live-set of each remaining level from the failing entry's claimed trace —
donation off at L1+, the failing call's exact extents at L3 — and the
ladder jumps straight to the first level predicted to fit the device
capacity, skipping levels *proven* still too big (the prediction is a
lower bound, so predicted ≥ capacity is a proof). Every jump logs
``predicted_peak_bytes``/``capacity_bytes``/``skipped_levels`` in its
``compile_deopt`` event. Capacity: ``THUNDER_TPU_HBM_BYTES`` override →
backend ``memory_stats()['bytes_limit']`` → the DeviceSpec datasheet.

Also here: the cheap post-step isfinite guard (``jit(on_nan=...)``) —
on a non-finite output the failing step is re-run once **instrumented**
under a NaN watcher so the producing op is attributed before raising
(:class:`NonFiniteOutputError`) or warning.

Knobs: ``THUNDER_TPU_MAX_RECOVERY_ATTEMPTS`` (default 4),
``THUNDER_TPU_RETRY_BACKOFF_S`` (base, default 0.05; doubles per attempt,
capped at 2s — set 0 in tests).
"""

from __future__ import annotations

import logging
import os
import time
from typing import Optional

from thunder_tpu.observability import events as obs_events
from thunder_tpu.observability import metrics as obsm
from thunder_tpu.resilience import demotion

logger = logging.getLogger("thunder_tpu")

MAX_LEVEL = 3

_LEVEL_ACTIONS = {
    1: "disable fusion/donation",
    2: "aggressive rematerialization",
    3: "exact shapes (no bucket padding)",
}


def max_attempts() -> int:
    try:
        return int(os.environ.get("THUNDER_TPU_MAX_RECOVERY_ATTEMPTS", "4"))
    except ValueError:
        return 4


def _backoff_s(attempt: int) -> float:
    try:
        base = float(os.environ.get("THUNDER_TPU_RETRY_BACKOFF_S", "0.05"))
    except ValueError:
        base = 0.05
    return min(base * (2 ** attempt), 2.0)


def current_level(cd) -> int:
    return getattr(cd, "_deopt_level", 0)


# Process-wide high-water mark of the ladder: any function de-opted means
# this process is trading speed for survival — the /healthz deopt component
# (observability/opsplane.py) reads it without enumerating CompileDatas.
_process_state = {"max_level": 0}


def process_max_level() -> int:
    return _process_state["max_level"]


def reset_process_state() -> None:
    """Tests only: the high-water mark is process-wide by design."""
    _process_state["max_level"] = 0


def _planned_peaks(entry, cs, cd=None):
    """(predicted per-level peak bytes, device capacity bytes) for the
    failing entry's claimed trace — the static liveness planner's input to
    level selection (analysis/liveness.py). (None, None) when no trace or
    capacity is known (the ladder then climbs blind, exactly as before)."""
    from thunder_tpu.common import CACHE_OPTIONS

    trace = None
    sym_spec = None
    true_extents = None
    if entry is not None:
        sym_spec = entry.sym_spec
        true_extents = getattr(entry, "last_true_extents", None)
        if entry.computation_traces:
            trace = entry.computation_traces[-1]
    if trace is None and cs is not None and getattr(cs, "last_traces", None):
        trace = cs.last_traces[-1]
    if trace is None:
        return None, None
    from thunder_tpu.analysis.liveness import (
        device_capacity_bytes,
        predict_level_peaks,
    )

    capacity = device_capacity_bytes()
    if not capacity:
        return None, None
    # Without an entry in hand (a failure during the build itself) we may
    # hold a stale trace of a symbolic-cache function whose sym_spec we
    # cannot see — L3 must stay unprovable rather than inherit L1's peak.
    bucketing_unknown = (
        entry is None
        and getattr(cd, "cache_option", None) is CACHE_OPTIONS.SYMBOLIC_VALUES
    )
    peaks = predict_level_peaks(
        trace,
        sym_spec=sym_spec,
        donated=trace.tags.get("donated_inputs") or (),
        true_extents=true_extents,
        bucketing_unknown=bucketing_unknown,
    )
    return peaks, capacity


def _choose_level(peaks: dict, capacity: int, base: int):
    """First ladder level above ``base`` whose predicted peak fits the
    capacity, skipping levels the planner *proves* still won't fit (the
    prediction is a lower bound: predicted >= capacity ⇒ the real run is
    certainly bigger). Unknown peaks (None) are never skipped. When no
    level fits, fall back to the blind single-step climb — the planner is
    advisory, the ladder still terminates the same way."""
    skipped: list[int] = []
    for level in range(base + 1, MAX_LEVEL + 1):
        p = peaks.get(level)
        if p is None or p < capacity:
            return level, p, skipped
        skipped.append(level)
    # Nothing fits: blind one-step climb. No prediction attached — the
    # resulting compile_deopt must not look planner-guided (consumers
    # detect guidance by field presence).
    return base + 1, None, []


def escalate(cd, reason: str, attempt: int, *, entry=None, cs=None) -> bool:
    """Bump ``cd``'s ladder position, record it, and sleep the backoff.
    False when the ladder is exhausted — the caller re-raises.

    With an OOM-shaped failure the static liveness planner
    (:func:`_planned_peaks`) prices each remaining level and the ladder
    jumps straight to the first one predicted to fit, instead of paying one
    failed ~20s XLA compile per level to discover the same thing; levels
    skipped this way are named in the ``compile_deopt`` event
    (``skipped_levels``), alongside ``predicted_peak_bytes``/
    ``capacity_bytes``."""
    base = current_level(cd)
    level = base + 1
    predicted = None
    capacity = None
    skipped: list[int] = []
    if level <= MAX_LEVEL and "oom" in reason:
        try:
            peaks, capacity = _planned_peaks(entry, cs, cd)
        except Exception:  # noqa: BLE001 — planning must never block recovery
            peaks = None
        if peaks and capacity:
            level, predicted, skipped = _choose_level(peaks, capacity, base)
    if level > MAX_LEVEL or attempt >= max_attempts():
        return False
    # With an autopilot installed (ISSUE 11), the climb is a policy
    # decision: the typed autopilot_decision (actuator deopt_escalate)
    # precedes the compile_deopt recovery event it correlates with, and
    # the escalation applies inside the serialized-recovery critical
    # section — a sidecar thread's de-opt cannot interleave with an
    # elastic resume in flight.
    import contextlib

    from thunder_tpu.resilience import autopilot as ap_mod

    ap = ap_mod.current()
    ctx = contextlib.nullcontext()
    if ap is not None:
        decision = ap.decide(ap_mod.Signal(
            "oom" if "oom" in reason else "compile_fail",
            evidence={"reason": reason, "level": level, "attempt": attempt},
        ))
        ctx = ap.recovery(decision)
    with ctx:
        cd._deopt_level = level
        if level > _process_state["max_level"]:
            _process_state["max_level"] = level
        backoff = _backoff_s(attempt)
        if obsm.enabled():
            obsm.COMPILE_DEOPTS.inc(level=str(level))
        # Planner fields appear ONLY on planner-guided escalations (a level
        # was priced or proven-skipped) — consumers detect guidance by field
        # presence, so blind climbs must not emit nulls or a lone capacity.
        planner = {}
        if predicted is not None or skipped:
            planner = {
                k: v
                for k, v in (("predicted_peak_bytes", predicted),
                             ("capacity_bytes", capacity),
                             ("skipped_levels", skipped or None))
                if v is not None
            }
        obs_events.emit_event(
            "compile_deopt",
            level=level,
            action=_LEVEL_ACTIONS.get(level, "?"),
            reason=reason,
            attempt=attempt,
            backoff_s=backoff,
            **planner,
        )
        if backoff:
            time.sleep(backoff)
    return True


# -- the recovery driver (called from api.fn_) ---------------------------------


_RECOVERY = {
    demotion.KERNEL: "demoting the claimed kernel executors and re-claiming on the next one down",
    demotion.COMPILE: "recompiling one de-opt level down",
    demotion.OOM: "recompiling one de-opt level down",
    demotion.CACHE_CORRUPT: "purging the compile cache and recompiling",
}


def _recovered(kind: str, exc: BaseException, where: str) -> bool:
    """Every recovery is said out loud, with the exception it recovered from:
    a caller who did not ask for it must be able to see that the result came
    from a demoted or de-optimized program (``cache_info(fn)`` and
    ``demotion.quarantine_snapshot()`` say which)."""
    logger.warning(
        "thunder_tpu recovered from a %s failure at %s by %s: %s: %s",
        kind, where, _RECOVERY[kind], type(exc).__name__, exc,
    )
    return True


def handle_compile_failure(exc: BaseException, cd, cs, attempt: int) -> bool:
    """Recovery decision for an exception raised while *building* an entry
    (tracing/claiming/staging). True → the caller retries the compile."""
    kind = demotion.classify_failure(exc)
    if kind in (demotion.COMPILE, demotion.OOM):
        ok = escalate(cd, f"compile failure: {kind}", attempt, cs=cs)
    elif kind == demotion.KERNEL:
        # A kernel executor raised while staging its claimed op: demote and
        # re-claim (no ladder bump needed — the program itself is fine).
        ok = _demote_from(exc, None, cs, attempt)
    elif kind == demotion.CACHE_CORRUPT:
        ok = _purge_compile_cache(exc, attempt)
    else:
        return False
    return ok and _recovered(kind, exc, "compile")


def handle_run_failure(exc: BaseException, cd, cs, entry, attempt: int) -> bool:
    """Recovery decision for an exception raised while *running* an entry
    (first run = the real XLA compile; warm run = kernel/device fault).
    Evicts the entry so the retry recompiles. True → caller retries."""
    kind = demotion.classify_failure(exc)
    if kind is None:
        return False
    _evict(cs, entry)
    if kind == demotion.KERNEL:
        extrace = entry.computation_traces[-1] if entry.computation_traces else None
        ok = _demote_from(exc, extrace, cs, attempt)
    elif kind in (demotion.COMPILE, demotion.OOM):
        ok = escalate(cd, f"run failure: {kind}", attempt, entry=entry, cs=cs)
    else:
        ok = _purge_compile_cache(exc, attempt)
    return ok and _recovered(kind, exc, "run")


def _demote_from(exc, extrace, cs, attempt: int) -> bool:
    if attempt >= max_attempts():
        return False
    pairs = demotion.failing_pairs(exc, extrace) if extrace is not None else []
    if not pairs:
        from thunder_tpu.resilience.chaos import InjectedKernelError

        if isinstance(exc, InjectedKernelError):
            # Staging-time raise: the trace is not in hand, but the injected
            # error names the executor — quarantine it for every op it could
            # have claimed by quarantining the (executor-wide) wildcard the
            # claiming pass also consults.
            return demotion.quarantine("*", exc.executor, reason=str(exc))
        return False
    demoted = False
    for sym_id, ex_name in pairs:
        demoted |= demotion.quarantine(sym_id, ex_name, reason=type(exc).__name__)
    return demoted


def _evict(cs, entry) -> None:
    try:
        cs.cache_entries.remove(entry)
    except ValueError:
        pass
    cs.fast_cache.clear()  # keys pointing at the dead entry regenerate


def _purge_compile_cache(exc, attempt: int) -> bool:
    if attempt >= max_attempts():
        return False
    from thunder_tpu.resilience import compile_cache

    return compile_cache.purge_on_error(exc)


# -- post-step isfinite guard --------------------------------------------------


class NonFiniteOutputError(RuntimeError):
    """``jit(on_nan=...)``: a step produced NaN/Inf. When the entry was
    re-run instrumented, ``symbol``/``line``/``provenance`` attribute the
    producing op."""

    def __init__(self, msg: str, *, symbol: Optional[str] = None,
                 line: Optional[str] = None, provenance: Optional[str] = None):
        self.symbol = symbol
        self.line = line
        self.provenance = provenance
        super().__init__(msg)


ON_NAN_MODES = ("raise", "rerun-instrumented", "warn")


def resolve_on_nan(value) -> Optional[str]:
    if value is None:
        return None
    value = str(value)
    if value not in ON_NAN_MODES:
        raise ValueError(
            f"on_nan: expected one of {ON_NAN_MODES} or None, got {value!r}"
        )
    return value


def outputs_finite(out) -> bool:
    """Cheap isfinite sweep over the float tensor leaves of a step output.
    The per-leaf reductions are folded into ONE device-side scalar so the
    common all-finite case pays a single host sync, not one per leaf."""
    import jax
    import jax.numpy as jnp

    from thunder_tpu.core.pytree import tree_flatten

    checks = [
        jnp.isfinite(x).all()
        for x in tree_flatten(out)[0]
        if isinstance(x, jax.Array) and jnp.issubdtype(x.dtype, jnp.floating)
    ]
    if not checks:
        return True
    if len(checks) == 1:
        return bool(checks[0])
    return bool(jnp.all(jnp.stack(checks)))


def handle_nonfinite(entry, inps: list, mode: str):
    """The ``on_nan`` policy after the guard tripped. ``rerun-instrumented``
    re-runs the SAME inputs once through the claimed trace bracketed with a
    NaN watcher, so the raise names the producing BoundSymbol, its generated
    line, and the pass that made it."""
    if obsm.enabled():
        obsm.NAN_GUARD_TRIPS.inc()
    obs_events.emit_event("nan_guard", action=mode)

    symbol = line = provenance = None
    if mode == "rerun-instrumented" and getattr(entry, "claimed_extrace", None) is not None:
        from thunder_tpu.executors.passes import del_last_used
        from thunder_tpu.observability.instrument import (
            NaNWatchError,
            NaNWatcher,
            instrument_for_execution,
        )

        watcher = NaNWatcher(mode="nan+inf")
        itrace = instrument_for_execution(entry.claimed_extrace, (watcher,))
        itrace = del_last_used(itrace)
        try:
            itrace.python_callable()(*inps)
        except NaNWatchError as e:
            symbol, line, provenance = e.sym_name, e.trace_line, e.provenance
            obs_events.emit_event(
                "nan_guard", action="attributed", symbol=symbol, line=line,
                provenance=provenance,
            )
    if mode == "warn":
        import warnings

        warnings.warn(
            "thunder_tpu: step produced non-finite outputs (on_nan='warn')",
            RuntimeWarning, stacklevel=3,
        )
        return
    detail = f" — produced by {symbol!r}: {line} [{provenance}]" if symbol else ""
    if symbol and getattr(entry, "sym_spec", None) is not None:
        # The instrumented re-run watches PADDED intermediates; an op whose
        # padding lanes legitimately produce inf/NaN can be named before
        # the true (cropped-extent) producer. Say so rather than misdirect.
        detail += (
            " (bucketed entry: the named op may be a padding-lane producer "
            "upstream of the true one)"
        )
    raise NonFiniteOutputError(
        f"step produced non-finite outputs (on_nan={mode!r}){detail}",
        symbol=symbol, line=line, provenance=provenance,
    )
