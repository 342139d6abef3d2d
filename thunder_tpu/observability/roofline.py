"""Continuous roofline ledger: duty-cycled in-loop profiling (ISSUE 19).

The attribution pipeline (``profile`` → ``attribute`` →
``join_cost_attribution``) is accurate but manual: someone has to run it,
read the table, and remember what it said last week. This module makes it
continuous. A :class:`RooflineSampler` rides the training loop and, every N
steps (``THUNDER_TPU_ROOFLINE_EVERY``, off by default), runs ONE step under
the existing :func:`~thunder_tpu.observability.profile.profile` bracket,
joins the measured per-op device time against the static cost model, and
folds the result into a bounded in-memory :class:`RooflineLedger`:

    op scope -> measured us/step, flops, bytes, roofline ceiling
    (``max(flops/peak, bytes/hbm_bw, comm/ici_bw)`` from analysis/cost),
    achieved-fraction, bound-class, and a trend over recent probes.

Every probe also streams each op's measured/predicted ratio into the ops
plane's :class:`~thunder_tpu.observability.detect.DetectorBank`
(``note_roofline_op``), so a mispriced cost model raises a typed
``cost_model_drift`` anomaly — and a regressed executor-claimed kernel a
``kernel_regression`` — in-run, not at the next manual profile. The live
ledger is served at ``/debug/roofline`` and printable via
``thunder_tpu.monitor.roofline_report()``; ``ROOFLINE_r*.json`` is the
per-op series of it that ``scripts/perf_report.py --gate`` enforces. docs/performance.md ("continuous roofline ledger") walks the
workflow.

Off-path cost: when no probe is due, :meth:`RooflineSampler.maybe_sample`
is one counter bump and a modulo — ``scripts/lint_traces.py --roofline``
gates it below 1% of a gpt-tiny CPU step. With ``every=0`` (the default)
no probe ever runs.
"""

from __future__ import annotations

import logging
import os
import shutil
import tempfile
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

log = logging.getLogger(__name__)

ENV_EVERY = "THUNDER_TPU_ROOFLINE_EVERY"

# The committed-artifact row schema: every ledger row (and every row of a
# ROOFLINE_r*.json round) carries exactly these fields. lint_traces
# --roofline and tests/test_roofline.py validate against this tuple.
ROW_FIELDS = (
    "label", "sym", "line", "measured_us", "flops", "bytes",
    "roofline_us", "achieved_frac", "bound", "share", "executor",
    "samples", "trend",
)

# |mean(newer half) - mean(older half)| of the achieved-fraction history
# below this is "flat" — achieved fractions live in [0, 1] so an absolute
# band beats a relative one near zero.
TREND_EPS = 0.05


@dataclass
class RooflineEntry:
    """One op scope's ledger row: the latest probe's measurement joined
    with its static bound, plus a bounded achieved-fraction history that
    classifies the trend across probes."""

    label: str
    sym: str
    line: int
    pass_name: Optional[str] = None
    measured_us: float = 0.0  # latest probe, per step
    share: float = 0.0  # of device-busy time, latest probe
    flops: Optional[float] = None
    bytes: Optional[float] = None
    roofline_us: Optional[float] = None  # static ceiling
    achieved_frac: Optional[float] = None  # roofline/measured, capped at 1
    bound: Optional[str] = None  # compute|memory|comm|free
    executor: Optional[str] = None  # claiming executor, None = inline jax
    samples: int = 0  # probes that saw this op
    last_ts: float = 0.0
    history: deque = field(
        default_factory=lambda: deque(maxlen=32), repr=False)

    @property
    def trend(self) -> str:
        """``improving`` / ``degrading`` / ``flat`` over the achieved-
        fraction history (newer-half mean vs older-half mean)."""
        h = [v for v in self.history if v is not None]
        if len(h) < 4:
            return "flat"
        half = len(h) // 2
        old = sum(h[:half]) / half
        new = sum(h[half:]) / (len(h) - half)
        if new - old > TREND_EPS:
            return "improving"
        if old - new > TREND_EPS:
            return "degrading"
        return "flat"

    def as_row(self) -> dict:
        """JSON-safe row in the committed ``ROW_FIELDS`` schema."""
        return {
            "label": self.label,
            "sym": self.sym,
            "line": self.line,
            "measured_us": round(self.measured_us, 3),
            "flops": self.flops,
            "bytes": self.bytes,
            "roofline_us": (
                round(self.roofline_us, 3)
                if self.roofline_us is not None else None),
            "achieved_frac": (
                round(self.achieved_frac, 4)
                if self.achieved_frac is not None else None),
            "bound": self.bound,
            "share": round(self.share, 4),
            "executor": self.executor,
            "samples": self.samples,
            "trend": self.trend,
        }


class RooflineLedger:
    """Bounded per-op ledger folded from probe joins.

    Keyed by scope label; at most ``max_ops`` entries — on overflow the
    cheapest op (smallest measured time) is evicted, since the ledger
    exists to watch the ops that own the step. Thread-compatible with the
    sampler's single-probe-at-a-time discipline; reads
    (:meth:`snapshot` / :meth:`rows`) copy under no lock because folds
    replace scalar fields atomically."""

    def __init__(self, *, max_ops: int = 256, history: int = 32,
                 clock: Callable[[], float] = time.time):
        self.max_ops = int(max_ops)
        self.history = int(history)
        self._clock = clock
        self._entries: dict[str, RooflineEntry] = {}
        self.folds = 0

    def __len__(self) -> int:
        return len(self._entries)

    def fold(self, join: Any, *,
             executor_by_sym: Optional[dict] = None) -> list[RooflineEntry]:
        """Fold one :class:`~thunder_tpu.observability.attribution.PerfJoin`
        (one probe) into the ledger; returns the entries it touched."""
        now = self._clock()
        touched: list[RooflineEntry] = []
        for row in join.rows:
            e = self._entries.get(row.label)
            if e is None:
                e = self._entries[row.label] = RooflineEntry(
                    label=row.label, sym=row.sym, line=row.line,
                    pass_name=row.pass_name,
                    history=deque(maxlen=self.history),
                )
            e.measured_us = float(row.measured_us)
            e.share = float(row.share)
            e.flops = row.flops
            e.bytes = getattr(row, "bytes_moved", None)
            e.roofline_us = row.roofline_us
            e.achieved_frac = row.efficiency
            e.bound = row.bound
            if executor_by_sym:
                e.executor = executor_by_sym.get(row.sym, e.executor)
            e.samples += 1
            e.last_ts = now
            e.history.append(row.efficiency)
            touched.append(e)
        while len(self._entries) > self.max_ops:
            cheapest = min(self._entries.values(), key=lambda x: x.measured_us)
            del self._entries[cheapest.label]
        self.folds += 1
        return touched

    def rows(self) -> list[RooflineEntry]:
        return sorted(self._entries.values(), key=lambda e: -e.measured_us)

    def snapshot(self) -> dict:
        """JSON-safe state for ``/debug/roofline`` and the bench artifact."""
        return {
            "folds": self.folds,
            "ops": len(self._entries),
            "schema": list(ROW_FIELDS),
            "rows": [e.as_row() for e in self.rows()],
        }

    def format(self, top_k: int = 10) -> str:
        lines = [
            f"roofline ledger: {len(self._entries)} op(s), "
            f"{self.folds} probe(s) folded",
            f"  {'op':<34} {'us/step':>9} {'achieved':>9} {'bound':>8} "
            f"{'trend':>10} {'n':>3}",
        ]
        for e in self.rows()[:top_k]:
            ach = (f"{e.achieved_frac * 100:.0f}%"
                   if e.achieved_frac is not None else "-")
            lines.append(
                f"  {e.label:<34.34} {e.measured_us:>9.1f} {ach:>9} "
                f"{e.bound or '-':>8} {e.trend:>10} {e.samples:>3}"
            )
        return "\n".join(lines)


class RooflineSampler:
    """Duty-cycled in-loop profiler feeding the ledger and the detectors.

    Wrap the step::

        sampler = monitor.roofline(jfn, every=200)
        for batch in data:
            loss = sampler.maybe_sample(jfn, params, batch)

    Every ``every``-th call runs under the profile bracket (one step, no
    warmup), attributes the trace back to scopes (annotated codegen +
    the compiled HLO text recovered from the jit cache entry), joins with
    ``trace_cost`` of the execution trace, folds into the ledger, and
    streams each op's measured/predicted ratio into the ops-plane
    :class:`~thunder_tpu.observability.detect.DetectorBank`. All other
    calls pay one counter bump. ``every <= 0`` (the default when
    ``THUNDER_TPU_ROOFLINE_EVERY`` is unset) never probes."""

    def __init__(self, jfn: Any = None, *, every: Optional[int] = None,
                 device: Any = None, hlo_text: Optional[str] = None,
                 ledger: Optional[RooflineLedger] = None,
                 bank: Any = None, step_name: str = "roofline_probe"):
        if every is None:
            try:
                every = int(os.environ.get(ENV_EVERY, "0") or 0)
            except ValueError:
                every = 0
        self.every = max(0, int(every))
        self.jfn = jfn
        self.device = device
        self.step_name = step_name
        self.ledger = ledger if ledger is not None else RooflineLedger()
        self._bank = bank
        self._hlo_text = hlo_text
        self._cost: Any = None
        self._executor_by_sym: Optional[dict] = None
        self._resolved = False
        self._step = 0
        self.probes = 0
        self.last_coverage: Optional[float] = None  # of the last probe's join

    # -- duty cycle ------------------------------------------------------------

    @property
    def enabled(self) -> bool:
        return self.every > 0

    def tick(self) -> bool:
        """Advance the duty cycle; True when the next step is a probe.
        This bump-and-modulo is the entire per-step cost when sampling is
        armed but no probe is due (gated < 1% of a step by
        ``lint_traces --roofline``)."""
        if self.every <= 0:
            return False
        self._step += 1
        return self._step % self.every == 0

    def maybe_sample(self, fn: Callable, *args, **kwargs) -> Any:
        """Call in place of ``fn(*args, **kwargs)``; returns ``fn``'s
        output either way. Probes when the duty cycle says so."""
        if not self.tick():
            return fn(*args, **kwargs)
        return self.sample(fn, *args, **kwargs)

    # -- the probe -------------------------------------------------------------

    def _resolve(self, jfn: Any) -> None:
        """One-shot: recover the static half of the join from the jit
        compile stats — the execution trace prices via ``trace_cost``, the
        cache entry's computation lowers to the HLO text that maps raw op
        names back to scopes (required on backends whose trace events
        carry no scoped metadata, e.g. CPU), and the bound symbols name
        which executor claimed each sym."""
        if self._resolved:
            return
        self._resolved = True
        cs = getattr(jfn, "_lc_cs", None)
        if cs is None:
            log.warning(
                "roofline: %r has no compile stats (_lc_cs); probing "
                "without the static cost model — no ceilings, no drift "
                "detection", jfn)
            return
        try:
            if self._cost is None:
                from thunder_tpu.analysis.cost import trace_cost

                trace = cs.last_traces[-1]
                self._cost = trace_cost(trace, self.device)
                self._executor_by_sym = {
                    b.sym.name: b.sym.executor.name
                    for b in trace.bound_symbols
                    if getattr(b.sym, "executor", None) is not None
                }
            if self._hlo_text is None:
                entry = cs.cache_entries[-1]
                self._hlo_text = (
                    entry.computation_fn
                    .lower(*entry.hlo_audit_avals)
                    .compile().as_text())
        except Exception as e:
            log.warning("roofline: static-join setup failed (%s: %s); "
                        "continuing with what resolved", type(e).__name__, e)

    def sample(self, fn: Callable, *args, **kwargs) -> Any:
        """Run one probed step now (ignores the duty cycle): profile →
        attribute → join → fold → feed detectors. Returns ``fn``'s
        output; a failed join never fails the step."""
        from thunder_tpu.observability.profile import profile as profile_bracket

        self._resolve(self.jfn if self.jfn is not None else fn)
        box: dict[str, Any] = {}

        def _probe_step():
            box["out"] = fn(*args, **kwargs)
            return box["out"]

        trace_dir = tempfile.mkdtemp(prefix="thunder_tpu_roofline_")
        t0 = time.perf_counter()
        try:
            res = profile_bracket(
                _probe_step, trace_dir=trace_dir, steps=1, warmup=0,
                step_name=self.step_name)
            self.probes += 1
            try:
                from thunder_tpu.observability import metrics as obsm

                obsm.ROOFLINE_PROBES.inc_always()
            except Exception:
                pass
            touched: list[RooflineEntry] = []
            if res.get("profiler"):
                try:
                    join = self._join(trace_dir)
                    if join is not None:
                        self.last_coverage = join.attribution.coverage
                        touched = self.ledger.fold(
                            join, executor_by_sym=self._executor_by_sym)
                        self._feed_bank(touched)
                except Exception as e:
                    log.warning("roofline: probe join failed (%s: %s)",
                                type(e).__name__, e)
            try:
                from thunder_tpu.observability.events import emit_event

                emit_event(
                    "roofline_probe", step=self._step, ops=len(touched),
                    probe_s=round(time.perf_counter() - t0, 6))
            except Exception:
                pass
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        return box.get("out")

    def _join(self, trace_dir: str) -> Any:
        from thunder_tpu.observability.attribution import (
            attribute, join_cost_attribution)

        attr = attribute(trace_dir, hlo_text=self._hlo_text)
        if not attr.by_line:
            return None
        return join_cost_attribution(attr, self._cost, steps=1)

    def _feed_bank(self, touched: list[RooflineEntry]) -> None:
        bank = self._bank
        if bank is None:
            try:
                from thunder_tpu.observability import opsplane

                plane = opsplane.current()
                bank = plane.bank if plane is not None else None
            except Exception:
                bank = None
        if bank is None:
            return
        for e in touched:
            if e.roofline_us and e.measured_us:
                bank.note_roofline_op(
                    e.label, e.measured_us, e.roofline_us,
                    executor=e.executor)

    # -- introspection ---------------------------------------------------------

    def debug_state(self) -> dict:
        return {
            "enabled": self.enabled,
            "every": self.every,
            "steps": self._step,
            "probes": self.probes,
            "ledger": self.ledger.snapshot(),
        }


# =============================================================================
# Module singleton (the monitor-facade / ops-plane hookup)
# =============================================================================

_state: dict[str, Optional[RooflineSampler]] = {"sampler": None}


def current() -> Optional[RooflineSampler]:
    return _state["sampler"]


def enable(jfn: Any = None, *, every: Optional[int] = None,
           **kwargs) -> RooflineSampler:
    """Install (and return) the process-wide sampler —
    ``thunder_tpu.monitor.roofline(...)`` forwards here. ``every=None``
    reads ``THUNDER_TPU_ROOFLINE_EVERY`` (unset/0 = armed object, no
    probes)."""
    sampler = RooflineSampler(jfn, every=every, **kwargs)
    _state["sampler"] = sampler
    return sampler


def disable() -> None:
    _state["sampler"] = None


def debug_state() -> dict:
    """``/debug/roofline`` payload (also a key of ``/debug/state``)."""
    s = current()
    if s is None:
        return {"enabled": False}
    return s.debug_state()
