"""Examine: support reporting, trace inspection, static memory estimation.

Reference parity: thunder/examine/__init__.py (`examine:49` — reports which
torch ops in a callable are unsupported; `get_fusions:190`) and
examine/memory_caculation.py (`get_alloc_memory:120` — static peak-memory
estimate over a trace).
"""

from __future__ import annotations

from typing import Any, Callable, Optional

from thunder_tpu.analysis.cost import cost_report, trace_cost  # noqa: F401  (examine.cost_report)
from thunder_tpu.analysis.liveness import memory_report, plan_liveness  # noqa: F401  (examine.memory_report)
from thunder_tpu.core.prims import OpTags, PrimIDs
from thunder_tpu.core.proxies import TensorProxy, variableify
from thunder_tpu.core.pytree import tree_flatten
from thunder_tpu.core.trace import TraceCtx


def _collect_unsupported(fn: Callable, args, kwargs) -> tuple[list[str], Optional[str]]:
    """One eager pass under a recording TorchFunctionMode: every torch call
    is checked for ltorch coverage and then executed FOR REAL, so ALL
    unsupported ops are enumerated in a single run (reference:
    examine/__init__.py:17-49 — the same collector design). Returns
    (unsupported op names, user error or None)."""
    import torch
    from torch.overrides import TorchFunctionMode

    from thunder_tpu.core.langctxs import Languages, resolve_language
    from thunder_tpu.torch import torch_function_map

    fmap = torch_function_map()
    ctx = resolve_language(Languages.TORCH)
    seen: list[str] = []
    seen_set: set[str] = set()

    # Mirrors frontend/dispatch.py: mapped directly, or resolvable as an
    # ltorch method by name.
    def covered(func) -> bool:
        if func in fmap:
            return True
        name = getattr(func, "__name__", None)
        return bool(name and ctx.has_method(name))

    class Collector(TorchFunctionMode):
        def __torch_function__(self, func, types, f_args=(), f_kwargs=None):
            name = getattr(func, "__name__", "")
            # attribute-descriptor plumbing (Tensor.real's __get__ etc.) is
            # not an op the user wrote
            if not covered(func) and not (name.startswith("__") and name.endswith("__")):
                label = getattr(func, "__qualname__", name or repr(func))
                if label not in seen_set:
                    seen_set.add(label)
                    seen.append(label)
            return func(*f_args, **(f_kwargs or {}))

    user_error: Optional[str] = None
    try:
        with Collector():
            fn(*args, **kwargs)
    except Exception as e:  # noqa: BLE001 — eager failure is a USER bug, reported separately
        user_error = f"{type(e).__name__}: {e}"
    return seen, user_error


def examine(fn: Callable, *args, **kwargs) -> dict:
    """Report whether ``fn`` can be traced, and which torch operations are
    not supported (reference: examine/__init__.py:49).

    Torch-facing callables get the full collector pass — a model with three
    unsupported ops lists all three, and an exception raised by the model
    itself is reported as ``user_error`` rather than conflated with missing
    coverage. The acquisition itself is then attempted to produce a trace."""
    try:
        import torch
    except ImportError:
        torch = None

    from thunder_tpu.api import trace_program
    from thunder_tpu.frontend.module import ThunderModule

    unsupported: list[str] = []
    report: dict[str, Any] = {"supported": False, "unsupported_ops": unsupported, "trace": None}

    is_torch_module = torch is not None and isinstance(fn, torch.nn.Module)
    if is_torch_module:
        ops, user_error = _collect_unsupported(fn, args, kwargs)
        unsupported.extend(ops)
        if user_error is not None:
            report["user_error"] = user_error
        if unsupported or user_error:
            return report

    try:
        if is_torch_module:
            tm = ThunderModule(fn)
            entry = tm._compile(args, kwargs)
            comp = entry["traces"][0]
        else:
            _, comp = trace_program(fn, args, kwargs)
        report["supported"] = True
        report["trace"] = comp
    except NotImplementedError as e:
        unsupported.append(str(e))
    except Exception as e:  # noqa: BLE001
        report["error"] = f"{type(e).__name__}: {e}"
    return report


def lint(fn: Callable, *args, executors: Optional[Any] = None, verbose: bool = True, **kwargs) -> list:
    """Trace ``fn`` on the given example inputs, run the pass pipeline the
    dispatcher runs (``thunder_tpu/pipeline.py``, then del_last_used), and run
    the static verifier (thunder_tpu/analysis) over every stage. Returns the
    full list of :class:`~thunder_tpu.analysis.Diagnostic`s; with ``verbose``
    pretty-prints each one with the offending generated trace line.

    Unlike ``THUNDER_TPU_CHECKS=1`` (which raises at the first failing pass),
    lint collects everything — including warnings and info-level findings —
    so it doubles as a trace-quality report. Rule ids and the
    suppression/extension story: docs/trace_invariants.md.
    """
    from thunder_tpu import pipeline
    from thunder_tpu.analysis import attach_trace_lines, verify
    from thunder_tpu.api import trace_program
    from thunder_tpu.core.trace import debug_checks, mark
    from thunder_tpu.executors.passes import del_last_used
    from thunder_tpu.extend import resolve_executors

    # A thunder-compiled function: lint the UNDERLYING function (tracing the
    # wrapper would trace the dispatch machinery) and report its cache state
    # in the summary (ISSUE 2: cache observability).
    compiled = fn if getattr(fn, "_lc_cs", None) is not None else None
    cd = getattr(fn, "_lc_cd", None)
    if cd is not None:
        fn = cd.fn

    # The pipeline below must not raise mid-way even when THUNDER_TPU_CHECKS
    # is set globally — lint's contract is collect-everything.
    with debug_checks(False):
        # record_input_mutations=True as jit() has it: an input-mutating fn
        # gets the same {"__out", "__muts"} epilogue structure in its trace,
        # so lint verifies the program that would actually compile.
        plg, comp = trace_program(fn, args, kwargs, record_input_mutations=True)
        mark(comp, "Acquisition")
        mark(plg, "Prologue construction")
        cleaned = pipeline.clean(comp)
        made = pipeline.compile_trace(cleaned[-1], resolve_executors(executors)).traces
        extrace = del_last_used(made[-1])
        stages: list[TraceCtx] = [plg, comp, *cleaned, *made, extrace]

    diagnostics = []
    for trc in stages:
        diags = verify(trc)
        attach_trace_lines(diags, trc)
        diagnostics.extend(diags)

    if verbose:
        if not diagnostics:
            print(f"lint: {len(stages)} stages verified clean ({len(extrace.bound_symbols)} symbols)")
        for d in diagnostics:
            print(d.format())
        if compiled is not None:
            print(format_cache_report(compiled))
        from thunder_tpu.observability import metrics as obsm

        if obsm.enabled():
            print(format_metrics_report())
    return diagnostics


def hlo_report(fn: Callable, *args, device: Optional[Any] = None,
               verbose: bool = True, **kwargs):
    """Audit the compiled-HLO executable behind ``fn`` — the static view of
    what the XLA SPMD partitioner actually emitted (partitioner-inserted
    collectives, fusions, layout copies, host transfers, exposed wire time),
    which no trace-level tool can see (ROADMAP item 3).

    Accepts, in order of preference:

    - a ``thunder_tpu.jit``-compiled function: returns the report the
      ``hlo_audit`` compile phase attached to its latest cache entry,
      compiling on the example args first if needed;
    - an already-jitted jax callable (``jax.jit`` object or AOT
      ``Compiled``) — e.g. the ``build_train_step`` pjit step function:
      lowered and audited on the example args;
    - a plain callable: compiled through ``thunder_tpu.jit`` first.

    Returns the :class:`~thunder_tpu.analysis.hlo_audit.HloScheduleReport`;
    with ``verbose`` pretty-prints it plus the advisory ``hlo.*`` findings.
    Docs: docs/performance.md (§HLO auditor)."""
    from thunder_tpu.analysis.hlo_audit import audit_jitted

    report = None
    cs = getattr(fn, "_lc_cs", None)
    if cs is None and not hasattr(fn, "lower") and not hasattr(fn, "as_text"):
        from thunder_tpu.api import jit as _tt_jit

        fn = _tt_jit(fn)
        cs = fn._lc_cs
    if cs is not None:
        entry = cs.cache_entries[-1] if cs.cache_entries else None
        report = getattr(entry, "hlo_audit", None) if entry is not None else None
        if report is None:
            fn(*args, **kwargs)
            entry = cs.cache_entries[-1]
            report = getattr(entry, "hlo_audit", None)
        if report is None:
            # Compile-time audit disabled (THUNDER_TPU_HLO_AUDIT=0) or it
            # degraded to a sharp_edge — audit on demand from the captured
            # first-run avals.
            avals = getattr(entry, "hlo_audit_avals", None)
            if avals and hasattr(entry.computation_fn, "lower"):
                report = audit_jitted(entry.computation_fn, *avals, device=device)
        if report is None:
            raise RuntimeError(
                "no HLO audit available for this compiled function (the "
                "compile-time audit failed and no input avals were captured); "
                "see the sharp_edge events for the failure"
            )
    else:
        report = audit_jitted(fn, *args, device=device, **kwargs)
    if verbose:
        print(report.format())
        for d in report.diagnostics():
            print(d.format())
    return report


def format_cache_report(jfn: Callable) -> str:
    """Human-readable cache summary for a compiled function: aggregate and
    per-entry hit/miss/recompile counters plus trace/first-run seconds —
    recompile storms become visible instead of inferred."""
    from thunder_tpu.api import cache_info

    info = cache_info(jfn)
    lines = [
        f"cache[{info['cache_option']}]: {info['calls']} calls, "
        f"{info['hits']} hits ({info['fast_hits']} O(1) fast, {info['slow_hits']} "
        f"prologue-scan), {info['misses']} misses, {info['compiles']} compiles "
        f"({info['recompiles']} recompiles), {info['prologue_runs']} prologue runs",
        f"  trace {info['trace_seconds']:.3f}s, first-run (incl. XLA compile) "
        f"{info['first_run_seconds']:.3f}s, cache lookups "
        f"{info['cache_lookup_us_total']:.0f}us total",
    ]
    for e in info["entries"]:
        lines.append(
            f"  entry {e['index']} [{e['buckets']}]: {e['hits']} hits "
            f"({e['fast_hits']} fast), {e['prologue_runs']} prologue runs, "
            f"{e['guard_fails']} guard fails, trace {e['trace_s']:.3f}s, "
            f"first run {e['first_run_s']:.3f}s"
        )
    return "\n".join(lines)


def format_metrics_report() -> str:
    """One-screen summary of the process-wide observability metrics
    (``thunder_tpu.monitor``): compiles/recompiles, cache traffic, claim
    breakdown, padding waste — the cross-function counterpart of
    :func:`format_cache_report`. Empty series are elided."""
    from thunder_tpu.observability.metrics import REGISTRY

    flat = REGISTRY.report_compact()
    if not flat:
        return "metrics: enabled, no samples yet"
    lines = ["metrics (process-wide, thunder_tpu.monitor.report()):"]
    for name, v in flat.items():
        if isinstance(v, dict):  # histogram summary
            lines.append(
                f"  {name}: n={v['count']} mean={v['mean']:.1f} "
                f"min={v['min']:.1f} max={v['max']:.1f}"
            )
        else:
            lines.append(f"  {name}: {v}")
    return "\n".join(lines)


def get_fusions(trace: TraceCtx) -> list[tuple[str, Any]]:
    """Executor-claimed regions of a trace (reference: examine:190). Under
    whole-trace XLA staging every claimed bsym is one 'fusion seed'; returns
    (executor_name, bsym) pairs for non-default executors."""
    out = []
    for bsym in trace.bound_symbols:
        ex = bsym.sym.executor
        if ex is not None and ex.name not in ("python",):
            out.append((ex.name, bsym))
    return out


_DEL_IDS = {PrimIDs.DEL}
_NO_ALLOC_IDS = {
    PrimIDs.RETURN, PrimIDs.COMMENT, PrimIDs.PRINT,
    PrimIDs.UNPACK_TRIVIAL, PrimIDs.UNPACK_SEQUENCE, PrimIDs.UNPACK_KEY, PrimIDs.UNPACK_ATTR,
    PrimIDs.UNPACK_DIM,
    PrimIDs.CHECK_TENSOR_SHAPE_AND_METADATA, PrimIDs.CHECK_NUMBER_TYPE_AND_VALUE,
    PrimIDs.CHECK_STRING_VALUE, PrimIDs.CHECK_LEN, PrimIDs.CHECK_KEYS, PrimIDs.CHECK_NONE,
    PrimIDs.CHECK_DIM_BUCKET,
    PrimIDs.SHALLOW_COPY, PrimIDs.STOP_GRADIENT,
}


def get_alloc_memory(trace: TraceCtx) -> tuple[int, dict[str, int]]:
    """Static peak-allocation estimate over a trace in bytes
    (reference: examine/memory_caculation.py:120).

    Walks the program keeping a live-set of tensor buffers: inputs are live
    at entry, outputs of each bsym allocate, and ``del`` frees. Aliasing
    ops (shallow_copy/stop_gradient/views) are counted as allocations only
    when XLA would materialize them (reshape/transpose are not charged).
    """
    live: dict[str, int] = {}
    flat_args, _ = tree_flatten((trace.args, trace.kwargs))
    for a in flat_args:
        if isinstance(a, TensorProxy):
            live[a.name] = a.size_bytes

    peak = sum(live.values())
    timeline: dict[str, int] = {"inputs": peak}

    for i, bsym in enumerate(trace.bound_symbols):
        if bsym.sym.id in _DEL_IDS:
            for p in bsym.flat_proxy_args:
                live.pop(p.name, None)
            continue
        if bsym.sym.id in _NO_ALLOC_IDS:
            continue
        for o in bsym.flat_proxy_outs:
            if isinstance(o, TensorProxy) and o.name not in live:
                live[o.name] = o.size_bytes
        cur = sum(live.values())
        if cur > peak:
            peak = cur
            timeline[f"{i}:{bsym.sym.name}"] = cur
    return peak, timeline
