"""TraceCtx: the linear SSA-like program representation.

Reference parity: thunder/core/trace.py (`TraceCtx:46`, `python:309`,
`python_callable:400`, `from_trace:434`, tracectx contextvars `:453-474`,
`detached_trace:508`, `TraceProvenance:29`).

A trace is a list of ``BoundSymbol``s plus the signature (proxied args) and
output. It prints as valid Python and compiles to a callable. Every transform
is trace→trace and stamps a ``TraceProvenance`` so the full compilation
history is inspectable — reading the generated program is the primary
debugging tool, as in the reference.
"""

from __future__ import annotations

import contextvars
import time
from contextlib import contextmanager
from typing import Any, Callable, Optional, Sequence

from thunder_tpu.core import baseutils, codeutils
from thunder_tpu.core.baseutils import check
from thunder_tpu.core.codeutils import SigInfo
from thunder_tpu.core.proxies import Proxy, TensorProxy
from thunder_tpu.core.pytree import tree_flatten
from thunder_tpu.core.symbol import BoundSymbol


class TraceProvenance:
    def __init__(self, pss: str):
        self.pss = pss

    def __repr__(self) -> str:
        return f"# Constructed by {self.pss}"


class TraceCtx:
    def __init__(self, fn: Optional[Callable] = None, *, prologue: bool = False):
        self.fn = fn
        self.args: tuple = ()
        self.kwargs: dict = {}
        self.output: Any = None
        self.bound_symbols: list[BoundSymbol] = []
        self._scopes: list[list[BoundSymbol]] = [self.bound_symbols]
        self._names: set[str] = set()
        self._counter = baseutils.NamedCounter()
        self.provenance: Optional[TraceProvenance] = None
        self.name: str = "prologue" if prologue else "computation"
        self._siginfo: Optional[SigInfo] = None
        # Free-form metadata transforms may attach (e.g. saved_for_backward).
        self.tags: dict[str, Any] = {}
        # The region open while tracing (``region`` below), or None.
        self.region: Optional[str] = None

    # -- naming --------------------------------------------------------------

    def make_name(self, prefix: str = "t") -> str:
        while True:
            name = f"{prefix}{self._counter.next(prefix)}"
            if name not in self._names:
                self._names.add(name)
                return name

    def add_name(self, name: str) -> None:
        # Strict: the trace IR is SSA, so a name registered twice means two
        # proxies would alias one name — the verifier's ssa rules depend on
        # registration being unique (reference: trace.py add_name raises too).
        check(
            name not in self._names,
            lambda: f"Name {name!r} is already registered in this trace",
            ValueError,
        )
        self._names.add(name)

    def has_name(self, name: str) -> bool:
        return name in self._names

    # -- scopes --------------------------------------------------------------

    def push_scope(self, scope: list) -> None:
        self._scopes.append(scope)

    def pop_scope(self) -> list:
        check(len(self._scopes) > 1, "Cannot pop the root scope")
        return self._scopes.pop()

    @property
    def current_scope(self) -> list:
        return self._scopes[-1]

    def add_bound_symbol(self, bsym: BoundSymbol) -> None:
        self.current_scope.append(bsym)

    # -- signature -----------------------------------------------------------

    @property
    def siginfo(self) -> SigInfo:
        if self._siginfo is not None:
            return self._siginfo
        params = []
        for a in self.args:
            if isinstance(a, Proxy):
                params.append(a.name)
            else:
                params.append(codeutils.prettyprint(a))
        return SigInfo(self.name, params)

    def set_siginfo(self, siginfo: SigInfo) -> None:
        self._siginfo = siginfo

    # -- codegen -------------------------------------------------------------

    def pass_name(self) -> Optional[str]:
        """The provenance pass name without its timing suffix (``"Transform
        for execution"`` from ``"Transform for execution (took 3.2 ms)"``) —
        the one parsing point shared by annotated codegen and
        instrumentation attribution (observability/instrument.py)."""
        if self.provenance is None:
            return None
        pss = self.provenance.pss
        cut = pss.find(" (took")
        return pss[:cut] if cut >= 0 else pss

    def _annotate_tag(self) -> str:
        """Compact pass-provenance tag for profiler scope names: the pass
        name with spaces collapsed — e.g. "Transform_for_execution"."""
        pss = self.pass_name()
        return (pss or self.name).replace(" ", "_")

    def python(self, *, print_depth: int = 1, include_header: bool = True, annotate: bool = False) -> str:
        """Render the trace as Python source. ``annotate=True`` wraps each
        value-producing op in ``jax.named_scope`` so op names flow into HLO
        metadata and profiler timelines (reference: thunder/core/profile.py:15
        `add_markers` via torch.profiler/NVTX, env THUNDER_ANNOTATE_TRACES).
        The scope name carries the trace-line index and the pass provenance
        (``L<idx>.<sym>#<pass>``), so a profiler row maps back to BOTH the
        generated line and the transform that produced it
        (docs/observability.md). The separator is ``#`` — not ``@`` — because
        JAX's name stack silently truncates scope names at ``@`` before they
        reach HLO metadata, which would drop the pass provenance from every
        profile (observability/attribution.py parses both spellings)."""
        lines: list[str] = []
        if include_header:
            if self.provenance is not None:
                lines.append(repr(self.provenance))
            lines.append("import thunder_tpu.core.dtypes as dtypes")
            lines.append("import thunder_tpu.core.devices as devices")
            lines.append("")
        lines.append(self.siginfo.prettyprint())
        body: list[str] = []
        tag = self._annotate_tag() if annotate else ""
        open_region = None  # consecutive lines of one region share one ``with``
        for i, bsym in enumerate(self.bound_symbols):
            depth = 1 if bsym.region is None else 2
            if annotate and bsym.flat_proxy_outs:
                scope = f"L{i}.{bsym.sym.name}#{tag}"
                lines_of = [f"{baseutils.indent(depth)}with __annotate_scope({scope!r}):"]
                lines_of.extend(bsym.python(indent=depth + 1, print_depth=print_depth))
            else:
                lines_of = bsym.python(indent=depth, print_depth=print_depth)
            if not lines_of:
                continue
            if bsym.region != open_region:
                open_region = bsym.region
                if open_region is not None:
                    body.append(f"{baseutils.indent(1)}with __region({open_region!r}):")
            body.extend(lines_of)
        if not body:
            body = [f"{baseutils.indent(1)}pass"]
        lines.extend(body)
        return "\n".join(lines) + "\n"

    def gen_ctx(self) -> dict[str, Any]:
        """Build the exec namespace: every call target of every top-level
        bound symbol, plus dtypes/devices modules and per-bsym call ctx."""
        from thunder_tpu.core import dtypes, devices

        ctx: dict[str, Any] = {"dtypes": dtypes, "devices": devices}
        for bsym in self.bound_symbols:
            if bsym.sym.python_printer is not None:
                ctx.update(bsym._call_ctx)
                continue
            name, target = bsym.gen_call_target()
            if isinstance(target, tuple):  # (module label, module object)
                label, mod = target
                ctx[label] = mod
            else:
                existing = ctx.get(name)
                check(
                    existing is None or existing is target,
                    lambda: f"Name collision in generated code: {name}",
                )
                ctx[name] = target
            ctx.update(bsym._call_ctx)
        return ctx

    def python_callable(self, **exec_ctx) -> Callable:
        import os

        def _env_flag(name: str) -> bool:
            return os.environ.get(name, "").lower() not in ("", "0", "false", "off")

        # Either spelling enables annotation; an explicitly-disabled legacy
        # var ("0") must not shadow the new one.
        annotate = _env_flag("THUNDER_ANNOTATE_TRACES") or _env_flag("THUNDER_TPU_ANNOTATE_TRACES")
        source = self.python(include_header=False, annotate=annotate)
        ctx = self.gen_ctx()
        if annotate:
            import jax

            ctx["__annotate_scope"] = jax.named_scope
        if any(bsym.region is not None for bsym in self.bound_symbols):
            import jax

            ctx["__region"] = jax.named_scope
        ctx.update(exec_ctx)
        fn = baseutils.compile_and_exec(self.siginfo.name, source, ctx)
        fn.__thunder_trace__ = self
        return fn

    def __repr__(self) -> str:
        return self.python()


def from_trace(trc: TraceCtx) -> TraceCtx:
    """A new empty trace inheriting signature/names from ``trc``
    (reference: trace.py `from_trace:434`)."""
    new = TraceCtx(trc.fn)
    new.args = trc.args
    new.kwargs = trc.kwargs
    new.output = trc.output
    new.name = trc.name
    new._siginfo = trc._siginfo
    new._names = set(trc._names)
    new._counter = trc._counter  # share so fresh proxies never collide
    new.tags = dict(trc.tags)
    return new


# -- tracing context management ----------------------------------------------

_tracectx = contextvars.ContextVar("tracectx", default=None)

# Trace-level grad mode (torch.no_grad/enable_grad during acquisition):
# False ⇒ Symbol.__call__ detaches op outputs via stop_gradient, matching
# eager's "values computed under no_grad are leaves" semantics.
_grad_mode_ctx = contextvars.ContextVar("trace_grad_mode", default=True)


def get_tracectx() -> Optional[TraceCtx]:
    return _tracectx.get()


def set_tracectx(trace: TraceCtx):
    return _tracectx.set(trace)


def reset_tracectx(token) -> None:
    _tracectx.reset(token)


@contextmanager
def tracectx(trace: Optional[TraceCtx]):
    tok = _tracectx.set(trace)
    try:
        yield trace
    finally:
        _tracectx.reset(tok)


@contextmanager
def region(name: str):
    """Name a region of the program being traced, from the model's own code:
    every bound symbol recorded inside carries ``name`` (``BoundSymbol.region``,
    its decomposition's symbols too), claiming and the passes that rewrite with
    ``from_bsym`` keep it, and the generated program runs those lines under
    ``jax.named_scope(name)``, so the name is in the HLO's metadata and on the
    profile's rows whichever executor ran the line. Regions nest as ``a/b``.
    The backward that ``transforms/autodiff.py`` writes for a symbol of region
    ``r`` carries ``r/bwd``; other symbols a pass makes anew (a ``del``) carry
    none. Outside a trace this does nothing."""
    trace = get_tracectx()
    if trace is None:
        yield
        return
    outer = trace.region
    trace.region = name if outer is None else f"{outer}/{name}"
    try:
        yield
    finally:
        trace.region = outer


@contextmanager
def detached_trace():
    """A fresh throwaway trace context (reference: trace.py:508)."""
    trace = TraceCtx()
    with tracectx(trace):
        yield trace


# -- debug checks (the trace verifier's pipeline hook) ------------------------
#
# Every pass stamps provenance through wrap_in_trace_provenance/mark; with
# checks enabled, that stamping point ALSO runs the static verifier
# (thunder_tpu/analysis) on the pass output, so the first malformed trace is
# attributed to the pass that introduced it instead of surfacing as a cryptic
# codegen or runtime failure. Enabled per-compile via jit(debug_checks=True)
# (the contextvar) or process-wide via THUNDER_TPU_CHECKS=1.

_debug_checks_ctx = contextvars.ContextVar("trace_debug_checks", default=None)


def debug_checks_enabled() -> bool:
    v = _debug_checks_ctx.get()
    if v is not None:
        return v
    import os

    return os.environ.get("THUNDER_TPU_CHECKS", "").strip().lower() not in ("", "0", "false", "off")


@contextmanager
def debug_checks(enabled: Optional[bool]):
    """Scope the verifier on (True) or off (False); None defers to the
    enclosing scope / THUNDER_TPU_CHECKS environment variable."""
    if enabled is None:
        yield
        return
    tok = _debug_checks_ctx.set(bool(enabled))
    try:
        yield
    finally:
        _debug_checks_ctx.reset(tok)


def _maybe_verify(trc: TraceCtx) -> TraceCtx:
    if debug_checks_enabled():
        from thunder_tpu.analysis import verify_or_raise

        verify_or_raise(trc)
    return trc


def _record_pass(pass_name: str, elapsed_ms: Optional[float], trc: TraceCtx) -> None:
    """Observability tap on the provenance-stamping point every pass already
    flows through: per-pass duration → metrics histogram + a "pass" event in
    the JSONL log, correlated to the enclosing compile. Both sinks are
    no-ops (one flag/contextvar check) when observability is off."""
    from thunder_tpu.observability import events, metrics as obsm

    if obsm.enabled() and elapsed_ms is not None:
        obsm.PASS_MS.observe(elapsed_ms, **{"pass": pass_name})
    if events.active_log() is not None:
        events.emit_event(
            "pass",
            compile_id=events.current_compile_id(),
            name=pass_name,
            ms=elapsed_ms,
            n_bsyms=len(trc.bound_symbols),
            trace=trc.name,
        )


def wrap_in_trace_provenance(trc: TraceCtx, pass_name: str, start_ns: int) -> TraceCtx:
    elapsed_ms = (time.perf_counter_ns() - start_ns) / 1e6
    trc.provenance = TraceProvenance(f"{pass_name} (took {elapsed_ms:.2f} ms)")
    _record_pass(pass_name, elapsed_ms, trc)
    return _maybe_verify(trc)


def mark(trc: TraceCtx, pass_name: str) -> TraceCtx:
    trc.provenance = TraceProvenance(pass_name)
    _record_pass(pass_name, None, trc)
    return _maybe_verify(trc)
