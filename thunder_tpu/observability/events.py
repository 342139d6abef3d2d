"""Structured JSONL event log.

Every durable compilation-pipeline happening — compile start/end, per-pass
durations (from the PR 1 provenance hooks in ``core/trace.py``), cache
misses, bucket selection, sharp-edge observations, NaN-watch trips, profile
brackets — is one JSON object on one line, so logs stream, tail, and replay
(``scripts/lint_traces.py --events`` / ``thunder_tpu.analysis.events``).

Activation:

- process-wide: ``THUNDER_TPU_EVENTS=<path>`` (checked lazily, once);
- per-function: ``jit(fn, events="<path>")`` — that function's compiles and
  cache events go to its own log, overriding the global one.

Schema (stable; the replay tool validates it):

    {"v": 1, "ts": <unix seconds>, "seq": <per-log counter>, "kind": "...",
     "pid": <os pid>, "host": <jax.process_index() or 0>,
     ...kind-specific fields...}

``pid``/``host`` identify the writer so per-host logs of a multi-host job
merge deterministically (``scripts/lint_traces.py --events h0.jsonl h1.jsonl``).

Kind-specific required fields live in ``thunder_tpu.analysis.events.SCHEMA``.
Emission is a no-op costing one dict lookup when no log is active.

Ops plane (ISSUE 15): when ``observability/opsplane`` is enabled it
installs **taps** here — the flight-recorder ring and the streaming
detector bank see every emitted record, with or without a JSONL log
configured. With the plane off (the default) the taps tuple is empty and
every emit path pays exactly one module-global truth test; the dispatch
fast path emits nothing and pays nothing.
"""

from __future__ import annotations

import contextlib
import contextvars
import json
import os
import sys
import threading
import time
from typing import Any, Optional

SCHEMA_VERSION = 1

# -- ops-plane taps (observability/opsplane installs; empty = plane off) -------
# A tuple of ``tap(kind, fields)`` callables that see every emitted record,
# independent of whether a JSONL sink is configured — the flight recorder's
# ring and the detector bank. One module-global truth test when empty.
_ops: dict[str, Any] = {"taps": (), "recorder": None}


def set_ops_taps(taps: tuple, *, recorder=None) -> None:
    """Install (or clear, with ``()``) the ops-plane event taps. ``recorder``
    is the flight recorder :func:`flight_dump` delegates to."""
    _ops["taps"] = tuple(taps)
    _ops["recorder"] = recorder


def ops_active() -> bool:
    return bool(_ops["taps"])


def _tap(kind: str, fields: dict) -> None:
    for tap in _ops["taps"]:
        try:
            tap(kind, fields)
        except Exception:
            # The ops plane observes the workload; it must never take it
            # down — a detector/recorder bug degrades to silence.
            pass


def tap_event(kind: str, fields: dict) -> None:
    """Feed the ops taps directly — for emit sites that write through a
    specific :class:`EventLog` handle (which taps on its own) but skip
    emitting entirely when no log is configured; the flight recorder must
    still see those records."""
    if _ops["taps"]:
        _tap(kind, fields)


def flight_dump(reason: str = "manual"):
    """Dump the installed flight recorder's ring (``flightrec-<ts>-
    <reason>.jsonl``); None when the ops plane is off. The spelling fault
    sites use (watchdog timeout, SDC exhaustion, autopilot halt, unhandled
    dispatch faults) — one global probe when off, never raises."""
    rec = _ops["recorder"]
    if rec is None:
        return None
    try:
        return rec.dump(reason)
    except Exception:
        return None


_identity: dict[str, Any] = {}


def host_identity() -> dict[str, Any]:
    """``{"pid", "host"}`` stamped into every event record so per-host JSONL
    logs from a multi-host job can be merged with stable ordering
    (``thunder_tpu.analysis.events.merge_event_logs``). ``host`` is
    ``jax.process_index()`` when the jax backend is already up at the FIRST
    emission, else 0 — and then FROZEN: merge ordering and compile-id
    correlation key on (host, pid), so one process's events must never flip
    identity mid-log (pid disambiguates processes even when several froze
    host=0). Observability must also never be the thing that initializes
    the backend, hence asking only an existing one."""
    pid = os.getpid()
    if _identity.get("pid") != pid:
        # Fork-safety: a forked worker is a new writer and re-resolves.
        _identity.clear()
        _identity["pid"] = pid
        host = 0
        jax_mod = sys.modules.get("jax")
        if jax_mod is not None:
            try:
                # Only ask an already-initialized backend; process_index()
                # on a cold jax would trigger backend init from inside an
                # emit() call.
                if jax_mod._src.xla_bridge._backends:  # type: ignore[attr-defined]
                    host = int(jax_mod.process_index())
            except Exception:
                pass
        _identity["host"] = host
    return {"pid": pid, "host": _identity["host"]}


class EventLog:
    """Append-only JSONL sink. Opens lazily, one line per event, flushed per
    write (a crashed process keeps everything emitted before the crash).

    Construct via :func:`log_for_path` — one shared instance per path, so
    two functions logging to the same file share one handle and one ``seq``
    counter (independent instances would interleave duplicate seq values)."""

    def __init__(self, path: str):
        self.path = path
        self._f = None
        self._seq = 0
        self._lock = threading.Lock()
        self._dead = False

    def emit(self, kind: str, **fields) -> None:
        # Ops-plane taps see the record whether or not the sink survives:
        # the flight recorder is most valuable exactly when the disk log is
        # dying underneath it.
        if _ops["taps"]:
            _tap(kind, fields)
        # Observability must never take the workload down: a sink I/O
        # failure (unwritable path, disk full) warns once and disables this
        # log instead of crashing the compile/training step it observes.
        if self._dead:
            return
        rec = {"v": SCHEMA_VERSION, "ts": time.time(), "kind": kind}
        rec.update(host_identity())
        rec.update(fields)
        try:
            with self._lock:
                if self._f is None:
                    d = os.path.dirname(os.path.abspath(self.path))
                    if d:
                        os.makedirs(d, exist_ok=True)
                    self._f = open(self.path, "a")
                rec["seq"] = self._seq
                self._f.write(json.dumps(rec, default=str))
                self._f.write("\n")
                self._f.flush()
                self._seq += 1
        except OSError as e:
            self._dead = True
            # Silent observability loss must itself be observable: the drop
            # counter increments past the metrics gate so monitor.report()
            # shows it even when metrics were never enabled (ISSUE 6).
            from thunder_tpu.observability import metrics as obsm

            obsm.EVENT_LOG_DROPPED.inc_always()
            import warnings

            warnings.warn(
                f"thunder_tpu event log {self.path!r} disabled after I/O "
                f"failure: {e}",
                stacklevel=3,
            )

    def close(self) -> None:
        with self._lock:
            if self._f is not None:
                self._f.close()
                self._f = None


# -- active-log resolution ----------------------------------------------------

_active_log: contextvars.ContextVar[Optional[EventLog]] = contextvars.ContextVar(
    "thunder_tpu_event_log", default=None
)
_global = {"path": None, "log": None}
_logs_by_path: dict[str, EventLog] = {}


def log_for_path(path: str) -> EventLog:
    """The shared :class:`EventLog` for ``path`` (one instance per absolute
    path process-wide — keeps the per-log ``seq`` counter monotonic when
    several functions log to the same file)."""
    key = os.path.abspath(path)
    log = _logs_by_path.get(key)
    if log is None:
        log = _logs_by_path[key] = EventLog(path)
    return log


def set_global_path(path: Optional[str]) -> None:
    """Point the process-wide log somewhere (None disables). Mostly for
    tests; production uses THUNDER_TPU_EVENTS."""
    _global["path"] = path
    _global["log"] = log_for_path(path) if path else None
    _global["resolved"] = True


def _global_log() -> Optional[EventLog]:
    if not _global.get("resolved"):
        try:
            # While a trace is acquired ``os.environ`` is the sharp-edge
            # interceptors' stand-in (frontend/sharp.py), whose reads report
            # through ``active_log``: the log's own configuration comes from
            # the mapping it wraps.
            environ = getattr(os.environ, "_real", os.environ)
            path = environ.get("THUNDER_TPU_EVENTS", "").strip()
            _global["path"] = path or None
            _global["log"] = log_for_path(path) if path else None
        finally:
            # Checked once, also when the read raised: the next caller must
            # not pay for (or re-raise) the same failure.
            _global["resolved"] = True
    return _global["log"]


def active_log() -> Optional[EventLog]:
    log = _active_log.get()
    if log is not None:
        return log
    return _global_log()


def emit_event(kind: str, **fields) -> None:
    """Emit to the active log (contextvar override, else the global
    THUNDER_TPU_EVENTS log); no-op when neither is configured — except the
    ops-plane taps, which see every record even with no log (the flight
    recorder keeps context without paying full event logging)."""
    log = active_log()
    if log is not None:
        log.emit(kind, **fields)  # taps fire inside emit
    elif _ops["taps"]:
        _tap(kind, fields)


def emit_compile_end(
    compile_id, fn_name: str, ms: float, trace=None, *,
    symbolic: bool = False, recompile: bool = False, staged: bool = True,
) -> None:
    """The one writer of ``compile_end`` records, shared by the functional
    pipeline (api._compile_entry_checked) and the module frontend
    (frontend/module.py) so the schema cannot diverge between producers.
    ``trace`` is the final execution trace; its ``claim_breakdown`` /
    ``collective_bytes`` tags (stamped by executors/passes.py) become the
    event's executor and collective payloads."""
    log = active_log()
    if log is None and not _ops["taps"]:
        return
    tags = getattr(trace, "tags", None) or {}
    fields = dict(
        compile_id=compile_id,
        fn=fn_name,
        ms=ms,
        n_bsyms=len(trace.bound_symbols) if trace is not None else None,
        claims=tags.get("claim_breakdown") or {},
        collective_bytes=int(tags.get("collective_bytes") or 0),
        symbolic=symbolic,
        recompile=recompile,
        staged=staged,
    )
    if log is not None:
        log.emit("compile_end", **fields)  # taps fire inside emit
    else:
        # No sink configured, ops plane on: the recompile-rate detector and
        # the flight ring still need the record.
        _tap("compile_end", fields)


@contextlib.contextmanager
def event_scope(log: Optional[EventLog]):
    """Route ``emit_event`` to ``log`` within the scope (None = no change)."""
    if log is None:
        yield
        return
    tok = _active_log.set(log)
    try:
        yield
    finally:
        _active_log.reset(tok)


# -- compile correlation ------------------------------------------------------
# Per-pass events fire deep inside core/trace.py with no compile handle in
# scope; a contextvar carries the compile id so one compile's pass events
# correlate in the log.

_compile_id: contextvars.ContextVar[Optional[int]] = contextvars.ContextVar(
    "thunder_tpu_compile_id", default=None
)
_compile_seq = {"n": 0}


def current_compile_id() -> Optional[int]:
    return _compile_id.get()


@contextlib.contextmanager
def compile_scope(log: Optional[EventLog] = None):
    """Allocate a process-unique compile id, route events to ``log`` (when
    given), and yield the id. Used by ``api._compile_entry``."""
    _compile_seq["n"] += 1
    cid = _compile_seq["n"]
    tok = _compile_id.set(cid)
    try:
        with event_scope(log):
            yield cid
    finally:
        _compile_id.reset(tok)
