"""CompileData / CompileStats / CacheEntry.

Reference parity: thunder/common.py (`CompileData:138`, `CompileStats:54`,
`CacheEntry` in thunder/__init__.py:281) and thunder/core/options.py
(CACHE_OPTIONS, SHARP_EDGES_OPTIONS).
"""

from __future__ import annotations

import contextlib
import contextvars
import enum
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Optional, Sequence


class CACHE_OPTIONS(enum.Enum):
    NO_CACHING = enum.auto()
    CONSTANT_VALUES = enum.auto()
    SAME_INPUT = enum.auto()
    SYMBOLIC_VALUES = enum.auto()  # reserved, as in the reference


_string_to_cache_option = {
    "no caching": CACHE_OPTIONS.NO_CACHING,
    "constant values": CACHE_OPTIONS.CONSTANT_VALUES,
    "same input": CACHE_OPTIONS.SAME_INPUT,
    "symbolic values": CACHE_OPTIONS.SYMBOLIC_VALUES,
}


def resolve_cache_option(x: Any) -> CACHE_OPTIONS:
    if isinstance(x, CACHE_OPTIONS):
        return x
    if isinstance(x, str):
        opt = _string_to_cache_option.get(x.lower())
        if opt is not None:
            return opt
    raise ValueError(f"Unknown cache option {x!r}")


class SHARP_EDGES_OPTIONS(enum.Enum):
    ALLOW = enum.auto()
    WARN = enum.auto()
    ERROR = enum.auto()


_string_to_sharp_edges = {
    "allow": SHARP_EDGES_OPTIONS.ALLOW,
    "warn": SHARP_EDGES_OPTIONS.WARN,
    "error": SHARP_EDGES_OPTIONS.ERROR,
}


def resolve_sharp_edges_option(x: Any) -> SHARP_EDGES_OPTIONS:
    if isinstance(x, SHARP_EDGES_OPTIONS):
        return x
    if isinstance(x, str):
        opt = _string_to_sharp_edges.get(x.lower())
        if opt is not None:
            return opt
    raise ValueError(f"Unknown sharp_edges option {x!r} (allow|warn|error)")


class ThunderSharpEdgeWarning(UserWarning):
    """A tracing-unsafe construct was observed (reference:
    thunder/core/options.py:146 + jit_ext.py `_general_jit_sharp_edge:468`)."""


class ThunderSharpEdgeError(RuntimeError):
    """sharp_edges='error': a tracing-unsafe construct was observed."""


_sharp_edges_policy = contextvars.ContextVar(
    "sharp_edges_policy", default=SHARP_EDGES_OPTIONS.ALLOW
)


_sharp_edges_suppressed = contextvars.ContextVar("sharp_edges_suppressed", default=False)


@contextlib.contextmanager
def suppress_sharp_edges():
    """Scope for framework-internal work during tracing (e.g. guarded
    concretization) whose own env/clock reads are not USER sharp edges."""
    tok = _sharp_edges_suppressed.set(True)
    try:
        yield
    finally:
        _sharp_edges_suppressed.reset(tok)


def sharp_edge(msg: str) -> None:
    """Report a tracing-unsafe construct per the active policy. ALLOW is
    silent (the reference's default); WARN emits ThunderSharpEdgeWarning;
    ERROR raises ThunderSharpEdgeError.

    The body runs with reporting suppressed: while a trace is acquired the
    clocks and ``os.environ`` are the interceptors' (frontend/sharp.py), and
    what the reporter reads for itself (the log's configuration, an event's
    timestamp, ``warnings``' filters) is not a sharp edge of the user's."""
    if _sharp_edges_suppressed.get():
        return
    with suppress_sharp_edges():
        policy = _sharp_edges_policy.get()
        # Observability tap (before the ALLOW early-return: the event log wants
        # every sharp edge, the policy only governs warn/raise behavior).
        from thunder_tpu.observability import events, metrics as obsm

        if obsm.enabled():
            obsm.SHARP_EDGES.inc()
        if events.active_log() is not None:
            events.emit_event("sharp_edge", message=msg, policy=policy.name.lower())
        if policy is SHARP_EDGES_OPTIONS.ALLOW:
            return
        full = (
            f"sharp edge: {msg}. The trace specializes on the observed value; "
            f"changes to it will NOT recompile. Pass sharp_edges='allow' to silence."
        )
        if policy is SHARP_EDGES_OPTIONS.ERROR:
            raise ThunderSharpEdgeError(full)
        import warnings

        warnings.warn(full, ThunderSharpEdgeWarning, stacklevel=3)


@contextlib.contextmanager
def sharp_edges_policy(policy: SHARP_EDGES_OPTIONS):
    tok = _sharp_edges_policy.set(policy)
    try:
        yield
    finally:
        _sharp_edges_policy.reset(tok)


@dataclass
class CompileData:
    """Options resolved at jit() time (reference: thunder/common.py:138)."""

    fn: Callable
    executors_list: tuple = ()
    cache_option: CACHE_OPTIONS = CACHE_OPTIONS.CONSTANT_VALUES
    sharp_edges: SHARP_EDGES_OPTIONS = SHARP_EDGES_OPTIONS.ALLOW
    disable_jit_staging: bool = False
    is_module: bool = False
    compile_options: dict = field(default_factory=dict)
    # Distributed state (set by thunder_tpu.parallel transforms)
    use_ddp: bool = False
    use_fsdp: bool = False
    process_group: Any = None
    _used_options: dict = field(default_factory=dict)

    def get_compile_option(self, name: str, doc: str) -> Any:
        self._used_options[name] = doc
        return self.compile_options.get(name)

    def last_compile_options(self) -> dict:
        return dict(self._used_options)


class EntryStats:
    """Per-cache-entry counters (ISSUE 2: cache observability)."""

    __slots__ = ("hits", "fast_hits", "prologue_runs", "guard_fails", "trace_s",
                 "first_run_s", "degradation_level", "phases",
                 "predicted_peak_bytes")

    def __init__(self):
        self.hits = 0  # times this entry served a call
        self.fast_hits = 0  # ... of which via the O(1) key fast path
        self.prologue_runs = 0  # times this entry's prologue executed
        self.guard_fails = 0  # prologue/value-guard rejections during probes
        self.trace_s = 0.0  # host tracing+transform time building this entry
        self.first_run_s = 0.0  # first execution (includes the XLA compile)
        # De-opt ladder position this entry compiled at (resilience/deopt.py):
        # 0 normal, 1 no fusion/donation, 2 + aggressive remat, 3 + exact
        # shapes. Surfaced per entry by thunder_tpu.cache_info.
        self.degradation_level = 0
        # Compile-phase spans (seconds) of this entry's build: trace /
        # transforms / claim / static_analysis / staging / xla_compile, plus
        # the persistent XLA cache verdict ("persistent_cache": "hit"|"miss")
        # when jax's cache resolved the first run. Mirrors the compile_phase
        # events.
        self.phases: dict = {}
        # Static liveness planner's predicted per-device peak HBM for this
        # entry (analysis/liveness.py; None when planning failed or was
        # skipped) — what the de-opt ladder consults to jump levels.
        self.predicted_peak_bytes = None

    def as_dict(self) -> dict:
        return {k: getattr(self, k) for k in self.__slots__}


@dataclass
class CacheEntry:
    """One compiled specialization (reference: thunder/__init__.py:281)."""

    prologue_fn: Callable
    computation_fn: Callable
    epilogue_fn: Optional[Callable]
    backward_fn: Optional[Callable]
    prologue_traces: list
    computation_traces: list
    backward_traces: list
    return_none_instead_of_grads: bool = False
    torch_facing: bool = False
    needs_rng: bool = False
    # Guards over input-derived scalar values that the trace specialized on
    # (core/concrete.py): all must re-evaluate equal for a cache hit.
    value_guards: tuple = ()
    # Symbolic-values caching (core/bucketing.SymbolicSpec) — None for exact
    # entries. When set, dispatch pads marked dims to the bucket ceiling,
    # appends true-extent scalars for masked reductions, and crops outputs.
    sym_spec: Any = None
    # Shape-class record for automatic symbolic-dim detection: the flatten
    # treedef and per-leaf metadata of the inputs this entry was built from.
    treedef: Any = None
    leaf_meta: tuple = ()
    # Post-step isfinite guard policy (jit(on_nan=...)): None disables the
    # check; "rerun-instrumented" re-runs via claimed_extrace — the claimed
    # (pre-instrumentation, pre-del) execution trace — under a NaN watcher
    # to attribute the producing op (resilience/deopt.py).
    on_nan: Any = None
    claimed_extrace: Any = None
    # The compile_scope id this entry was built under: the first run happens
    # after the scope exits, so the xla_compile phase event needs the id
    # carried explicitly to correlate with the build's compile_phase events.
    compile_id: Any = None
    # Lazily-resolved "L<idx>.<sym>" labels of the execution trace's
    # collective dispatch sites (None = not yet computed, () = none): what
    # the collective watchdog names in a CollectiveTimeoutError and the
    # gate deciding whether a dispatch is guarded at all (api._run_entry).
    collective_lines: Any = None
    # Static planner artifacts (ISSUE 10; api._compile_entry_impl's
    # static_analysis phase): the schedule certificate the watchdog's
    # timeout diagnosis consumes, and the last call's true bucket extents
    # (set per dispatch) so the de-opt ladder can price the L3 exact-shape
    # level for the failing call.
    schedule_certificate: Any = None
    last_true_extents: Any = None
    stats: EntryStats = field(default_factory=EntryStats)


class CompileStats:
    """Timers, caches, trace history (reference: thunder/common.py:54)."""

    def __init__(self):
        self.cache_entries: list[CacheEntry] = []
        self.cache_hits: int = 0
        self.cache_misses: int = 0
        self.calls: int = 0
        self.last_traces: list = []
        self.last_prologue_traces: list = []
        self.last_backward_traces: list = []
        # O(1) dispatch fast path: (treedef, leaf metadata) -> CacheEntry,
        # learned on the first slow (prologue-scanning) hit for a key. Bounded;
        # cleared wholesale on overflow (keys regenerate on the next slow hit).
        self.fast_cache: dict = {}
        self.fast_hits: int = 0
        self.slow_hits: int = 0
        self.prologue_runs: int = 0
        # Compile-side counters/accumulators (ISSUE 2: cache observability).
        self.compile_count: int = 0
        self.trace_seconds: float = 0.0
        self.first_run_seconds: float = 0.0
        self.cache_lookup_ns: int = 0
        # nanosecond timers
        self.last_trace_host_start: int = 0
        self.last_trace_host_stop: int = 0
        self.last_trace_cache_start: int = 0
        self.last_trace_cache_stop: int = 0
        self.last_trace_tracing_start: int = 0
        self.last_trace_tracing_stop: int = 0
        self.last_trace_host_execution_start: int = 0
        self.last_trace_host_execution_stop: int = 0

    @property
    def last_compile_time_ms(self) -> float:
        return (self.last_trace_tracing_stop - self.last_trace_tracing_start) / 1e6

    @property
    def recompile_count(self) -> int:
        """Compiles beyond the first — the recompile-storm signal."""
        return max(0, self.compile_count - 1)

    @property
    def last_cache_lookup_us(self) -> float:
        return (self.last_trace_cache_stop - self.last_trace_cache_start) / 1e3


def timer_ns() -> int:
    return time.perf_counter_ns()
