"""The claiming pass and codegen-adjacent passes.

Reference parity: thunder/executors/passes.py (`transform_for_execution:131`
— operator-executor claiming, fusion passes, always-executors —
and `del_last_used:232`).

Claiming walks each top-level bound symbol: the first executor in priority
order whose checker accepts it claims it whole; otherwise the pass descends
into the symbol's decomposition (subsymbols). Terminal prims must be claimed
by someone (the JAX executor covers all of them).

Which passes run before the claim, and in which order, is not said here:
``thunder_tpu/pipeline.py`` owns that list, and every front end calls it.
"""

from __future__ import annotations

import copy
import time
from typing import Sequence

from thunder_tpu.core.baseutils import check
from thunder_tpu.core.prims import OpTags, PrimIDs
from thunder_tpu.core.proxies import Proxy, variableify
from thunder_tpu.core.pytree import tree_flatten
from thunder_tpu.core.symbol import BoundSymbol, Symbol
from thunder_tpu.core.trace import TraceCtx, from_trace, wrap_in_trace_provenance
from thunder_tpu.extend import Executor, FusionExecutor, get_always_executors

_PASSTHROUGH_IDS = {
    PrimIDs.DEL,
    PrimIDs.RETURN,
    PrimIDs.COMMENT,
    PrimIDs.UNPACK_TRIVIAL,
    PrimIDs.UNPACK_SEQUENCE,
    PrimIDs.UNPACK_KEY,
    PrimIDs.UNPACK_ATTR,
    PrimIDs.UNPACK_DIM,  # printer emits `d = t.shape[i]`, any backend
    PrimIDs.TENSOR_CONSTANT,  # printer emits a _call_ctx binding, any backend
}


def _claimed(sym: Symbol, ex: Executor) -> Symbol:
    new = copy.copy(sym)
    new.executor = ex
    return new


def would_claim(bsym: BoundSymbol, executors: Sequence[Executor]):
    """The name of the executor the claiming pass would give ``bsym`` whole
    to, or None: asked in its order, of the checkers alone (no fuel is spent),
    so a transform may ask before it rewrites for an executor."""
    from thunder_tpu.resilience.demotion import is_quarantined

    for ex in executors:
        if not is_quarantined(bsym.sym.id, ex.name) and ex.accepts(bsym):
            return ex.name
    return None


def transform_for_execution(trace: TraceCtx, executors_list: Sequence[Executor]) -> TraceCtx:
    """Claim every bound symbol and run the fusion passes."""
    start = time.perf_counter_ns()
    executors_list = tuple(executors_list) + get_always_executors()
    new_bsyms: list[BoundSymbol] = []

    # Executor demotion (resilience/demotion.py): a (sym, executor) pair
    # quarantined after a kernel failure is skipped here, so the re-claim
    # walks down the priority list to jaxex/pythonex until the TTL expires.
    from thunder_tpu.resilience.demotion import is_quarantined

    def claim(bsym: BoundSymbol, depth: int = 0) -> None:
        if bsym.sym.id in _PASSTHROUGH_IDS:
            new_bsyms.append(bsym)
            return
        for ex in executors_list:
            if is_quarantined(bsym.sym.id, ex.name):
                continue
            if ex.can_execute(bsym):
                new_bsyms.append(bsym.from_bsym(sym=_claimed(bsym.sym, ex)))
                return
        if bsym.sym.python_impl is not None:
            # Host-side op with an inline implementation (guards etc.)
            new_bsyms.append(bsym)
            return
        if not bsym.subsymbols and not (
            bsym.has_tag(OpTags.SIDE_EFFECT) or bsym.has_tag(OpTags.DONT_DCE)
        ):
            # A composite whose decomposition recorded nothing is an identity
            # (e.g. ``x[...]`` with full slices, dropout(p=0)): its outputs
            # ARE its input proxies, so the op can simply be dropped — unless
            # it is tagged effectful, in which case dropping it would erase an
            # observable action (the verifier/DCE share this tag model).
            arg_vars = {variableify(p) for p in bsym.flat_proxy_args}
            if all(variableify(o) in arg_vars for o in bsym.flat_proxy_outs):
                return
        check(
            len(bsym.subsymbols) > 0,
            lambda: f"No executor for primitive {bsym.sym.qualname} (id {bsym.sym.id})",
        )
        for sub in bsym.subsymbols:
            claim(sub, depth + 1)

    for bsym in trace.bound_symbols:
        claim(bsym)

    extrace = from_trace(trace)
    extrace.bound_symbols = new_bsyms

    # Fusion executors run after claiming (reference: passes.py:145); on TPU
    # XLA is the fusion engine so this is typically a no-op hook.
    for ex in executors_list:
        if isinstance(ex, FusionExecutor):
            extrace = ex.fusion_pass(extrace)

    extrace.tags["claim_breakdown"] = _claim_breakdown(extrace)
    extrace.tags["collective_bytes"] = _collective_bytes(extrace)
    return wrap_in_trace_provenance(extrace, "Transform for execution", start)


def _claim_breakdown(trace: TraceCtx) -> dict[str, int]:
    """{executor name (or "host" for python_impl plumbing): claimed bsyms} —
    the observability subsystem's executor-claim metric/event payload."""
    out: dict[str, int] = {}
    for bsym in trace.bound_symbols:
        ex = bsym.sym.executor
        name = ex.name if ex is not None else "host"
        out[name] = out.get(name, 0) + 1
    return out


def _collective_bytes(trace: TraceCtx) -> int:
    """Static bytes moved by collectives (COMM_OP-tagged symbols), from the
    trace's tensor metadata: each collective is charged its tensor operands'
    sizes. A per-trace constant — the dispatcher multiplies by call counts."""
    from thunder_tpu.core.proxies import TensorProxy

    total = 0
    for bsym in trace.bound_symbols:
        if OpTags.COMM_OP not in bsym.sym.tags:
            continue
        for p in bsym.flat_proxy_args:
            if isinstance(p, TensorProxy):
                total += p.size_bytes
    return total


def del_last_used(trace: TraceCtx, *, clear_mutable_collections: bool = False) -> TraceCtx:
    """Insert ``del`` statements after each proxy's last use
    (reference: passes.py `del_last_used:232`).

    Under whole-trace XLA staging this is cosmetic for device memory (XLA
    buffer liveness governs), but it keeps host references from pinning
    donated arrays and preserves the reference's readable-trace contract.
    """
    from thunder_tpu.core import prims

    start = time.perf_counter_ns()
    flat_out, _ = tree_flatten(trace.output)
    keep = {variableify(p) for p in flat_out if isinstance(p, Proxy)}
    flat_args, _ = tree_flatten((trace.args, trace.kwargs))
    arg_vars = {variableify(p) for p in flat_args if isinstance(p, Proxy)}

    seen: set = set()
    rev: list[BoundSymbol] = []
    for bsym in reversed(trace.bound_symbols):
        if bsym.sym.id in (PrimIDs.DEL,):
            continue
        to_del = []
        for p in list(bsym.flat_proxy_args) + list(bsym.flat_proxy_outs):
            v = variableify(p)
            if v in seen or v in keep:
                continue
            seen.add(v)
            to_del.append(p)
        if to_del and bsym.sym.id not in (PrimIDs.RETURN,):
            rev.append(prims.python_del.bind(*to_del, output=None))
            rev[-1].region = bsym.region  # beside the last use, so that a region's lines stay one block
        rev.append(bsym)
    new_bsyms = list(reversed(rev))

    ntrace = from_trace(trace)
    ntrace.bound_symbols = new_bsyms
    return wrap_in_trace_provenance(ntrace, "Delete Last Used", start)
