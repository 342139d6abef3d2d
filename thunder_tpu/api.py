"""The jit entry point: acquisition → transforms → claiming → XLA staging.

Reference parity: thunder/__init__.py (`jit:299`, `get_computation_and_inputs:371`,
the prologue-guarded cache loop `:409-447`, `fn_:602`) and the functional
(eager-unpacking) frontend of thunder/functional.py (`jit:444`,
`_eager_unpacking_interpreter:301`).

TPU-first execution model: where the reference's generated Python dispatches
one torch/nvFuser call per line every iteration, here the generated trace
callable is staged **whole** under ``jax.jit`` at compile time — steady-state
cost is one guard re-execution plus one XLA executable launch (the
CUDA-graphs endgame, as the default).
"""

from __future__ import annotations

import collections
import functools
import time
from numbers import Number
from typing import Any, Callable, Optional, Sequence

from jax.profiler import TraceAnnotation

from thunder_tpu import clang  # registers the clang language  # noqa: F401
from thunder_tpu.common import (
    CACHE_OPTIONS,
    SHARP_EDGES_OPTIONS,
    CacheEntry,
    CompileData,
    CompileStats,
    resolve_cache_option,
    resolve_sharp_edges_option,
    sharp_edge,
    sharp_edges_policy,
    timer_ns,
)
from thunder_tpu.core import dtypes, prims
from thunder_tpu.core.baseutils import GuardFailure, check
from thunder_tpu.core.codeutils import SigInfo
from thunder_tpu.core.langctxs import Languages, langctx_ctx, resolve_language
from thunder_tpu.core.prims import OpTags, PrimIDs
from thunder_tpu.core.proxies import (
    CollectionProxy,
    NumberProxy,
    Proxy,
    StringProxy,
    TensorProxy,
    proxy,
    tensorproxy_from_concrete,
)
from thunder_tpu.core.pytree import tree_flatten, tree_map, tree_unflatten
from thunder_tpu.core.trace import TraceCtx, from_trace, tracectx
from thunder_tpu.executors import bridge, jaxex, pythonex  # register executors  # noqa: F401
from thunder_tpu.executors import flashex, pallasex  # higher-priority kernel executors  # noqa: F401
from thunder_tpu.executors import quantex  # opt-in int8 executor (registered, not default)  # noqa: F401
from thunder_tpu.executors.passes import del_last_used, transform_for_execution
from thunder_tpu import pipeline  # isort: skip  # after the executors: a transform imports pallasex, and the default order is the import order
from thunder_tpu.extend import resolve_executors
from thunder_tpu.observability import events as obs_events
from thunder_tpu.observability import metrics as obsm
from thunder_tpu.resilience import chaos as chaos_mod
from thunder_tpu.resilience import deopt as deopt_mod
from thunder_tpu.resilience import watchdog as watchdog_mod
from thunder_tpu.transforms.common import dce
from thunder_tpu.transforms.rng import RNG_TAG


# =============================================================================
# Acquisition (functional frontend)
# =============================================================================


def _proxy_input(x: Any, comp_trc: TraceCtx) -> Any:
    """Leaf → proxy, under the computation trace's name pool."""
    if bridge.is_concrete_tensor(x):
        return tensorproxy_from_concrete(x)
    if isinstance(x, (bool, int, float, complex, str)) or x is None:
        return x if x is None else proxy(x)
    if isinstance(x, Proxy):
        return x
    return proxy(x)  # AnyProxy


def _proxify_tree(tree: Any, comp_trc: TraceCtx) -> Any:
    if isinstance(tree, (tuple, list)):
        return type(tree)(_proxify_tree(v, comp_trc) for v in tree)
    if isinstance(tree, dict):
        return {k: _proxify_tree(v, comp_trc) for k, v in tree.items()}
    return _proxy_input(tree, comp_trc)


def _collect_leaves(proxied: Any, out: list) -> None:
    if isinstance(proxied, (tuple, list)):
        for v in proxied:
            _collect_leaves(v, out)
    elif isinstance(proxied, dict):
        for v in proxied.values():
            _collect_leaves(v, out)
    else:
        out.append(proxied)


def _build_prologue(
    args: tuple, kwargs: dict, proxied_args: tuple, proxied_kwargs: dict, tensor_leaves: list
) -> TraceCtx:
    """Construct the guard trace: unpack the input structure, validate every
    leaf's metadata/value, and return the flat tensor leaves.

    Reference parity: thunder/core/jit_ext.py `unpack_inputs:1098` — guards
    implement CONSTANT_VALUES caching: tensor metadata and Python-number
    values are checked; on mismatch the cache entry is skipped.
    """
    plg = TraceCtx(prologue=True)
    plg.name = "prologue"
    plg.set_siginfo(SigInfo("prologue", [], varargs="args", varkwargs="kwargs"))

    for t in tensor_leaves:
        plg.add_name(t.name)

    collections: list[CollectionProxy] = []

    def collection(concrete: Any, name: Optional[str] = None) -> CollectionProxy:
        collections.append(CollectionProxy(concrete, name=name))
        return collections[-1]

    with tracectx(plg):
        args_coll = collection(args, "args")
        kwargs_coll = collection(kwargs, "kwargs")

        from thunder_tpu.core.proxies import AnyProxy

        def slot_proxy(p: Any):
            """Unpack-output proxy for a leaf. None leaves get a fresh
            prologue-local AnyProxy so the slot can be guarded with
            check_none — a None→tensor change must be a controlled miss, not
            a silent reuse of the trace that baked the constant None in."""
            return AnyProxy(None, prefix="nil") if p is None else p

        def guard_leaf(p: Any, concrete: Any) -> None:
            if isinstance(p, TensorProxy):
                sdims = getattr(p, "_symbolic_dims", None)
                if sdims:
                    # Symbolic-values caching: marked dims guard only RANK here
                    # (None = wildcard extent); each marked dim is lifted into a
                    # NumberProxy and bucket-constrained, so one entry serves
                    # every extent in the bucket (core/bucketing.py).
                    shape_spec = tuple(
                        None if i in sdims else int(s) for i, s in enumerate(p.shape)
                    )
                    prims.check_tensor_shape_and_metadata(
                        p, shape_spec, str(p.device), p.true_dtype, p.requires_grad,
                        bridge.framework_of(concrete),
                    )
                    for i in sorted(sdims):
                        lo, hi, _cid = sdims[i]
                        d = prims.unpack_dim(p, i)
                        prims.check_dim_bucket(d, lo, hi)
                    return
                prims.check_tensor_shape_and_metadata(
                    p, tuple(p.shape), str(p.device), p.true_dtype, p.requires_grad, bridge.framework_of(concrete)
                )
            elif isinstance(p, NumberProxy):
                prims.check_number_type_and_value(p, p.value)
            elif isinstance(p, StringProxy):
                prims.check_string_value(p, p.value)
            elif isinstance(p, AnyProxy) and p.value is None:
                prims.check_none(p)
            else:
                # Unguardable leaf: its observed value is baked into the
                # trace with no prologue check — report per the sharp-edges
                # policy (reference: jit_ext.py `_general_jit_sharp_edge:468`).
                sharp_edge(
                    f"input {getattr(p, 'name', p)!r} of type "
                    f"{type(getattr(p, 'value', concrete)).__name__} cannot be guarded"
                )

        def unpack_into(coll_proxy: CollectionProxy, concrete: Any, proxied: Any) -> None:
            if isinstance(concrete, (tuple, list)):
                # Structural guard first: a different length raises GuardFailure
                # (controlled miss) instead of a raw unpack ValueError.
                prims.check_len(coll_proxy, len(concrete))
                outs = []
                sub = []  # (collproxy, concrete, proxied) to recurse
                leaf_slots = []  # (slot, concrete) to guard
                for c, p in zip(concrete, proxied):
                    if isinstance(c, (tuple, list, dict)):
                        cp = collection(c)
                        outs.append(cp)
                        sub.append((cp, c, p))
                    else:
                        slot = slot_proxy(p)
                        outs.append(slot)
                        leaf_slots.append((slot, c))
                bsym = prims.unpack_sequence.bind(coll_proxy, len(concrete), output=outs)
                plg.bound_symbols.append(bsym)
                for slot, c in leaf_slots:
                    guard_leaf(slot, c)
                for cp, c, p in sub:
                    unpack_into(cp, c, p)
            elif isinstance(concrete, dict):
                prims.check_keys(coll_proxy, tuple(concrete.keys()))
                for k, c in concrete.items():
                    p = proxied[k]
                    if isinstance(c, (tuple, list, dict)):
                        cp = collection(c)
                        bsym = prims.unpack_key.bind(coll_proxy, k, output=cp)
                        plg.bound_symbols.append(bsym)
                        unpack_into(cp, c, p)
                    else:
                        slot = slot_proxy(p)
                        bsym = prims.unpack_key.bind(coll_proxy, k, output=slot)
                        plg.bound_symbols.append(bsym)
                        guard_leaf(slot, c)
            else:
                raise NotImplementedError(f"Cannot unpack {type(concrete)}")

        if args:
            unpack_into(args_coll, args, proxied_args)
        else:
            prims.check_len(args_coll, 0)
        if kwargs:
            unpack_into(kwargs_coll, kwargs, proxied_kwargs)
        else:
            prims.check_len(kwargs_coll, 0)

        prims.python_return(tuple(tensor_leaves))

    # The first call's containers were needed to lay the unpacking out, and no
    # longer: a prologue that kept them would keep the caller's arrays alive (a
    # model's weights, gigabytes of them) for as long as the cache entry lives.
    for cp in collections:
        cp.coll = None
    plg.output = tuple(tensor_leaves)
    return plg


def _copy_container_tree(tree: Any) -> Any:
    """Structural copy (fresh containers, shared leaf proxies) — the pristine
    baseline for input-mutation detection."""
    if isinstance(tree, (tuple, list)):
        return type(tree)(_copy_container_tree(v) for v in tree)
    if isinstance(tree, dict):
        return {k: _copy_container_tree(v) for k, v in tree.items()}
    return tree


_MISSING = object()


def _mutation_value_spec(v: Any, extras: list):
    """Encode a mutated-in value: trace proxies become extra computation
    outputs (("out", j)); plain Python data is stored inline."""
    from thunder_tpu.core.proxies import pyval
    from thunder_tpu.core.symbol import resolve_inplace

    if isinstance(v, TensorProxy):
        extras.append(resolve_inplace(v))
        return ("out", len(extras) - 1)
    if isinstance(v, NumberProxy):
        return ("const", pyval(v))
    if isinstance(v, dict):
        return ("dict", {k: _mutation_value_spec(x, extras) for k, x in v.items()})
    if isinstance(v, (list, tuple)):
        tag = "list" if isinstance(v, list) else "tuple"
        return (tag, [_mutation_value_spec(x, extras) for x in v])
    return ("const", v)


def _same_container_type(a: Any, b: Any) -> bool:
    return (
        (isinstance(a, dict) and isinstance(b, dict))
        or (isinstance(a, list) and isinstance(b, list))
        or (isinstance(a, tuple) and isinstance(b, tuple))
    )


def _tuple_replaced(cur: tuple, orig: tuple) -> bool:
    """Did a tuple VALUE change? Tuples are immutable, so any leaf identity
    difference means the enclosing slot was rebound to a new tuple — the
    parent must record a wholesale set (recursion alone would drop it)."""
    if len(cur) != len(orig):
        return True
    for a, b in zip(cur, orig):
        if isinstance(a, tuple) and isinstance(b, tuple):
            if _tuple_replaced(a, b):
                return True
        elif _same_container_type(a, b):
            continue  # mutable containers inside tuples: diffed in place
        elif a is not b:
            return True
    return False


def _diff_container_tree(cur: Any, orig: Any, path: tuple, muts: list, extras: list) -> None:
    """Record container mutations fn made to its (proxied) inputs.

    Reference parity: thunder/core/jit_ext.py `process_recorded_modifications
    :1302` — the VM records STORE_SUBSCR et al.; here the proxied containers
    are diffed against a pristine structural copy after tracing. The pristine
    copy has FRESH container objects at every level, so container-typed
    values are compared by recursion, never by identity."""
    if isinstance(orig, dict) and isinstance(cur, dict):
        for k in orig:
            if k not in cur:
                muts.append(("del", path, k))
        for k, v in cur.items():
            ov = orig.get(k, _MISSING)
            if isinstance(v, tuple) and isinstance(ov, tuple):
                if _tuple_replaced(v, ov):
                    muts.append(("set", path, k, _mutation_value_spec(v, extras)))
                else:
                    _diff_container_tree(v, ov, path + (k,), muts, extras)
            elif _same_container_type(v, ov):
                _diff_container_tree(v, ov, path + (k,), muts, extras)
            elif ov is _MISSING or ov is not v:
                muts.append(("set", path, k, _mutation_value_spec(v, extras)))
    elif isinstance(orig, list) and isinstance(cur, list):
        if len(cur) != len(orig) or any(
            (a is not b and not _same_container_type(a, b))
            or (isinstance(a, tuple) and isinstance(b, tuple) and _tuple_replaced(a, b))
            for a, b in zip(cur, orig)
        ):
            muts.append(("resync", path, [_mutation_value_spec(v, extras) for v in cur]))
        else:
            for i, (a, b) in enumerate(zip(cur, orig)):
                _diff_container_tree(a, b, path + (i,), muts, extras)
    elif isinstance(orig, tuple) and isinstance(cur, tuple) and len(orig) == len(cur):
        # Top-level / nested positional structure: elements can't be rebound
        # in the CALLER (tuples are immutable), so recursion alone is right.
        for i, (a, b) in enumerate(zip(cur, orig)):
            _diff_container_tree(a, b, path + (i,), muts, extras)


def _collect_input_mutations(
    proxied_args, proxied_kwargs, pristine_args, pristine_kwargs, tensor_leaves
) -> tuple[list, list]:
    """(mutation records, extra output proxies) for epilogue replay.

    Two classes (reference: jit_ext.py:1302 + the input-mutation sharp edge
    at jit_ext.py:468): container mutations (``d["k"] = t``) and in-place
    tensor updates on INPUT tensors (``x.add_(1)``)."""
    from thunder_tpu.core.symbol import resolve_inplace

    muts: list = []
    extras: list = []
    _diff_container_tree(proxied_args, pristine_args, ("args",), muts, extras)
    _diff_container_tree(proxied_kwargs, pristine_kwargs, ("kwargs",), muts, extras)
    for i, p in enumerate(tensor_leaves):
        fp = resolve_inplace(p)
        if fp is not p:
            extras.append(fp)
            muts.append(("tensor", i, ("out", len(extras) - 1)))
    return muts, extras


def trace_program(
    fn: Callable, args: tuple, kwargs: dict, *, record_input_mutations: bool = False,
    symbolic_marks: Optional[dict] = None,
) -> tuple[TraceCtx, TraceCtx]:
    """Acquire ``fn`` as (prologue_trace, computation_trace).

    With ``record_input_mutations`` (the jit() path), mutations fn makes to
    its inputs (container writes, in-place tensor updates) are detected
    post-trace and recorded on ``comp_trc._input_mutations``; the
    computation output is then wrapped as ``{"__out": ..., "__muts": (...)}``
    so the staged program computes the final values and the caller replays
    them (CacheEntry.epilogue_fn). The module frontend has its own epilogue
    (frontend/module.py) and keeps this off."""
    comp_trc = TraceCtx(fn)
    comp_trc.name = "computation"

    with tracectx(comp_trc):
        proxied_args = _proxify_tree(args, comp_trc)
        proxied_kwargs = _proxify_tree(kwargs, comp_trc)
    pristine_args = _copy_container_tree(proxied_args)
    pristine_kwargs = _copy_container_tree(proxied_kwargs)

    # Canonical leaf order = jax.tree_util flatten order (sorted dict keys),
    # so grads, prologue outputs, and computation args all align with what
    # tree_flatten(params) gives the user.
    leaves, _ = tree_flatten((proxied_args, proxied_kwargs))
    tensor_leaves = [p for p in leaves if isinstance(p, TensorProxy)]

    if symbolic_marks:
        # cache="symbolic values": the caller traces on bucket-padded example
        # inputs; marked dims carry their bucket so the prologue guards
        # membership instead of the exact extent (core/bucketing.py).
        for li, dims in symbolic_marks.items():
            tensor_leaves[li]._symbolic_dims = dict(dims)

    comp_trc.args = tuple(tensor_leaves)
    # Concrete example inputs aligned with the tensor args: lets traced
    # Python coerce input-derived scalars (bool/int/float of a proxy) via
    # guarded concretization (core/concrete.py).
    flat_concrete, _ = tree_flatten((args, kwargs))
    comp_trc._concrete_leaves = [
        c for c, p in zip(flat_concrete, leaves) if isinstance(p, TensorProxy)
    ]

    from thunder_tpu.frontend.sharp import sharp_edge_interceptors

    with tracectx(comp_trc):
        with langctx_ctx(Languages.TORCH if _torch_lang_available() else Languages.CLANG), \
                sharp_edge_interceptors():
            result = fn(*proxied_args, **proxied_kwargs)
        if getattr(comp_trc, "_inplace_seen", False):
            # A returned proxy may have been updated in place after it was
            # produced — return its latest functional value.
            from thunder_tpu.core.symbol import resolve_inplace_tree

            result = resolve_inplace_tree(result)

        # Mutations are always DETECTED (so every staging path — jit, grad,
        # vmap/jvp — can see them on comp_trc._input_mutations); only the
        # jit() path (record_input_mutations) REPLAYS them via the epilogue.
        muts, extras = _collect_input_mutations(
            proxied_args, proxied_kwargs, pristine_args, pristine_kwargs, tensor_leaves
        )
        comp_trc._input_mutations = muts
        if muts and record_input_mutations:
            from thunder_tpu.common import sharp_edge

            kinds = sorted({m[0] for m in muts})
            sharp_edge(
                f"traced function mutates its inputs ({', '.join(kinds)}): the "
                "final values are replayed onto the caller's objects after "
                "execution (epilogue)"
            )
            result = {"__out": result, "__muts": tuple(extras)}
        prims.python_return(result)
    comp_trc.output = result

    # The prologue guards/unpacks the CALLER's structure — build it from the
    # pristine copies so fn's container mutations can't skew the guards.
    plg = _build_prologue(args, kwargs, pristine_args, pristine_kwargs, tensor_leaves)
    # Concretization is only possible while the user function executes; drop
    # the concrete-input references so cached trace objects don't pin the
    # first call's tensors (and params) for the process lifetime. Same for
    # the tensor-constant memo: its id-reuse guard matters only WHILE
    # tracing, and keeping it would pin every captured host tensor alongside
    # the baked device copy for the cache entry's lifetime.
    comp_trc._concrete_leaves = None
    if getattr(comp_trc, "_tconst_memo", None) is not None:
        comp_trc._tconst_memo = None
    return plg, comp_trc


def _torch_lang_available() -> bool:
    try:
        resolve_language(Languages.TORCH)
        return True
    except KeyError:
        return False


# =============================================================================
# Compilation
# =============================================================================


def _has_tag_in_trace(trc: TraceCtx, tag: OpTags) -> bool:
    return any(tag in b.sym.tags for b in trc.bound_symbols)


def _compile_entry(cd: CompileData, cs: CompileStats, args: tuple, kwargs: dict) -> CacheEntry:
    # debug_checks=True/False scopes the trace verifier (analysis/) over the
    # whole pass pipeline; None defers to THUNDER_TPU_CHECKS. Each pass's
    # provenance stamping (wrap_in_trace_provenance/mark in core/trace.py)
    # verifies its output, so a violation names the pass that introduced it.
    from thunder_tpu.core.trace import debug_checks
    from thunder_tpu.observability import events

    with debug_checks(cd.compile_options.get("debug_checks")), \
            events.compile_scope(getattr(cd, "_event_log", None)) as compile_id:
        events.emit_event(
            "compile_start",
            compile_id=compile_id,
            fn=getattr(cd.fn, "__name__", repr(cd.fn)),
            cache_option=cd.cache_option.name.lower(),
            call=cs.calls,
        )
        # De-opt ladder L3 (resilience/deopt.py): exact shapes — no bucket
        # padding — shrinks the entry's live memory after repeated OOMs.
        if (cd.cache_option is CACHE_OPTIONS.SYMBOLIC_VALUES
                and deopt_mod.current_level(cd) < 3):
            sym_spec = _symbolic_spec_for_call(cd, cs, args, kwargs)
            if sym_spec is not None:
                events.emit_event(
                    "bucket_select", compile_id=compile_id,
                    buckets=sym_spec.describe(),
                    marks={str(li): sorted(d.keys()) for li, d in sym_spec.marks.items()},
                )
                pargs, pkwargs = _pad_example(args, kwargs, sym_spec)
                return _compile_entry_checked(cd, cs, pargs, pkwargs, sym_spec,
                                              compile_id=compile_id)
        return _compile_entry_checked(cd, cs, args, kwargs, None, compile_id=compile_id)


def _compile_entry_checked(
    cd: CompileData, cs: CompileStats, args: tuple, kwargs: dict, sym_spec,
    compile_id: Optional[int] = None,
) -> CacheEntry:
    # De-opt ladder position (resilience/deopt.py): 0 = normal; ≥1 disables
    # fusion passes + buffer donation; ≥2 compiles under aggressive
    # rematerialization (scoped HERE so an aborted compile can't leak the
    # contextvar); ≥3 was applied upstream (exact shapes).
    deopt_level = deopt_mod.current_level(cd)
    if deopt_level >= 2:
        from thunder_tpu.transforms.rematerialization import aggressive_remat

        with aggressive_remat():
            return _compile_entry_impl(cd, cs, args, kwargs, sym_spec,
                                       compile_id, deopt_level)
    return _compile_entry_impl(cd, cs, args, kwargs, sym_spec, compile_id, deopt_level)


# Persistent-XLA-cache verdicts, tapped from jax's monitoring events: the
# compile_phase span for an entry's first run says whether the seconds went
# to a real backend compile (cache miss) or a cache-entry deserialize (hit)
# — the distinction that explains 2x swings in xla-compile totals between
# otherwise identical runs.
_jax_cache_events = {
    "hits": 0, "misses": 0, "backend_compile_s": 0.0, "cache_get_s": 0.0,
    "installed": False,
}


def _install_jax_cache_listener() -> None:
    if _jax_cache_events["installed"]:
        return
    _jax_cache_events["installed"] = True
    try:
        from jax._src import monitoring

        def _on_event(event: str, **kw) -> None:
            if event == "/jax/compilation_cache/cache_hits":
                _jax_cache_events["hits"] += 1
            elif event == "/jax/compilation_cache/cache_misses":
                _jax_cache_events["misses"] += 1

        def _on_duration(event: str, duration: float, **kw) -> None:
            if event == "/jax/core/compile/backend_compile_duration":
                _jax_cache_events["backend_compile_s"] += duration
            elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
                _jax_cache_events["cache_get_s"] += duration

        monitoring.register_event_listener(_on_event)
        monitoring.register_event_duration_secs_listener(_on_duration)
    except Exception:  # internal jax API: absence degrades to cache=None
        _jax_cache_events["installed"] = False


def _jax_cache_counts() -> dict:
    return {k: _jax_cache_events[k]
            for k in ("hits", "misses", "backend_compile_s", "cache_get_s")}


# Every compile-phase span of the process, both paths (``thunder_tpu.jit``
# and ``parallel.build_train_step``), always on and bounded: the gated sinks
# below need an operator to have asked before the compile ran; this is what
# ``compile_phases()`` hands out after the fact.
_compile_phase_records: collections.deque = collections.deque(maxlen=4096)


def compile_phases() -> list:
    """Every compile-phase span this process recorded, oldest first, as
    copies: ``{"program": compile id, "phase": name, "s": seconds, "at":
    time.perf_counter() when the span ended, ...extras}``. Holds the newest
    4096; ``cache_info(fn)["compile_phase_seconds"]`` is the per-function
    rollup (docs/observability.md, "Compile-phase spans")."""
    return [dict(r) for r in _compile_phase_records]


# One record a call of every ``thunder_tpu.jit`` function, always on and
# bounded, read by ``dispatch_phases()``; the same intervals are the
# ``thunder_tpu.call*`` spans on the profiler's trace.
_dispatch_records: collections.deque = collections.deque(maxlen=4096)


def dispatch_phases() -> list:
    """The dispatcher's parts of every call this process made, oldest first,
    as copies: ``{"fn": the function's __name__, "hit": "fast" | "slow" |
    "same_input" | "miss", "at": time.perf_counter() when the call returned,
    "s": the whole call, "lookup_s", "prepare_s", "launch_s"}``, and on a miss
    ``"compile_s"`` (the compile through the first run, which holds that
    run's prepare and launch). Seconds; holds the newest 4096
    (docs/observability.md, "Dispatch spans")."""
    return [dict(r) for r in _dispatch_records]


def _record_compile_phase(compile_id, phase: str, seconds: float, *,
                          log=None, **extra) -> None:
    """One compile-pipeline span: a record in the process-wide list
    ``compile_phases()`` reads, a ``compile_phase`` event (correlated by
    compile_id) + the ``thunder_tpu_compile_phase_s{phase=...}`` histogram.
    Together the spans decompose what ``thunder_tpu_xla_compile_s`` reports
    as one opaque number."""
    extra = {k: v for k, v in extra.items() if v is not None}
    _compile_phase_records.append(
        {"program": compile_id, "phase": phase, "s": seconds,
         "at": time.perf_counter(), **extra})
    if obsm.enabled():
        labels = {"phase": phase}
        if extra.get("cache"):
            labels["cache"] = extra["cache"]
        obsm.COMPILE_PHASE_S.observe(seconds, **labels)
    target = log if log is not None else obs_events.active_log()
    if target is not None:
        target.emit("compile_phase", compile_id=compile_id, phase=phase,
                    s=round(seconds, 6), **extra)
    else:
        # No JSONL sink: the ops-plane taps (flight ring) still get the
        # span — compile phases are exactly the context a fault dump needs.
        obs_events.tap_event("compile_phase", dict(
            compile_id=compile_id, phase=phase, s=round(seconds, 6), **extra))


def _compile_entry_impl(
    cd: CompileData, cs: CompileStats, args: tuple, kwargs: dict, sym_spec,
    compile_id: Optional[int], deopt_level: int,
) -> CacheEntry:
    import jax

    from thunder_tpu.core.trace import mark

    build_start = timer_ns()
    phases: dict[str, Any] = {}
    cs.compile_count += 1
    # Chaos seam: injected XLA compile failure/timeout — lands on the same
    # recovery path (the de-opt ladder) as the real thing.
    chaos_mod.compile_seam(getattr(cd.fn, "__name__", repr(cd.fn)))
    cs.last_trace_tracing_start = timer_ns()
    with sharp_edges_policy(cd.sharp_edges):
        plg_trc, comp_trc = trace_program(
            cd.fn, args, kwargs, record_input_mutations=True,
            symbolic_marks=sym_spec.marks if sym_spec is not None else None,
        )
    # Stamp (and, under debug checks, verify) the freshly acquired traces so
    # an acquisition bug is attributed to acquisition, not the first pass.
    mark(comp_trc, "Acquisition")
    mark(plg_trc, "Prologue construction")
    cs.last_trace_tracing_stop = timer_ns()
    phases["trace"] = (cs.last_trace_tracing_stop - cs.last_trace_tracing_start) / 1e9
    _phase_mark = timer_ns()

    input_mutations = getattr(comp_trc, "_input_mutations", None) or []
    if input_mutations and cd.compile_options.get("_trace_transforms"):
        raise NotImplementedError(
            "the traced function mutates its inputs, which cannot be combined "
            "with trace transforms (grad/value_and_grad/autocast) — make the "
            "function pure or apply updates outside it"
        )

    from thunder_tpu.core.concrete import value_guards_of

    value_guards = value_guards_of(comp_trc)

    computation_traces = [comp_trc, *pipeline.clean(comp_trc)]
    comp_trc = computation_traces[-1]

    if sym_spec is not None:
        # Thread validity masks through reductions over bucket-padded dims and
        # derive the output crop plan — BEFORE grad, so the masked program is
        # what gets differentiated (masks are constants w.r.t. the inputs).
        from thunder_tpu.transforms.padmask import thread_pad_masks

        comp_trc, mask_classes, crop_plan, pad_warnings = thread_pad_masks(comp_trc, sym_spec)
        comp_trc = dce(comp_trc)  # sweep replaced reductions' dead count constants
        computation_traces.append(comp_trc)
        sym_spec.mask_classes = mask_classes
        sym_spec.crop_plan = crop_plan
        if pad_warnings:
            import warnings

            for w in pad_warnings:
                warnings.warn(f"cache='symbolic values': {w}", stacklevel=2)

    # Trace-to-trace transforms requested at jit() time (grad, autocast, ...),
    # then the rewrites (not from de-opt ladder level 1, "disable fusion", up),
    # RNG functionalization and the claim: thunder_tpu/pipeline.py.
    trace_transforms = tuple(cd.compile_options.get("_trace_transforms", ()))
    if sym_spec is not None and trace_transforms:
        # The grad/autocast rewrite minted new output proxies (grads); re-run
        # the provenance analysis on the transformed trace so the crop plan
        # covers them exactly (transforms/padmask.py).
        from thunder_tpu.transforms.padmask import analyze_crop_plan

        def replanned(trc):
            sym_spec.crop_plan = analyze_crop_plan(trc, sym_spec)
            return trc

        trace_transforms += (replanned,)
    own_s = (timer_ns() - _phase_mark) / 1e9  # value guards and pad masks: the dispatcher's own
    compiled = pipeline.compile_trace(comp_trc, cd.executors_list, transforms=trace_transforms,
                                      rewrites=deopt_level < 1)
    computation_traces.extend(compiled.traces)
    extrace = compiled.claimed
    phases["transforms"] = own_s + compiled.seconds["transforms"]
    phases["claim"] = compiled.seconds["claim"]

    # Chaos seam: NaN-poison a chosen BoundSymbol (after claiming, so the
    # poison survives into both the staged entry and the instrumented
    # attribution re-run the on_nan guard performs).
    poisoned = chaos_mod.maybe_poison_nan(extrace)
    if poisoned is not extrace:
        extrace = poisoned
        computation_traces.append(extrace)
    # The claimed (pre-instrumentation, pre-del) trace: what the on_nan
    # guard re-runs under a NaN watcher to attribute a non-finite step.
    claimed_extrace = extrace

    # -- static planner suite (ISSUE 10) --------------------------------------
    # Runs on every compile (O(trace), its seconds are a gated compile phase):
    # stamps donation metadata on the claimed trace, predicts the per-device
    # peak HBM live-set (consulted by the de-opt ladder on an OOM), and
    # certifies the collective schedule (consumed by the watchdog's timeout
    # diagnosis and the sched.* verifier rule).
    _phase_mark = timer_ns()
    on_nan_opt = cd.compile_options.get("on_nan")
    # Resolved here (not at the staging block) because donation only happens
    # when the entry actually stages under jax.jit: an unstaged entry
    # (disable_jit_staging / device-sync ops / instrumentation) donates
    # nothing, and the planner must price — and the donation.* rules must
    # see — what will really run.
    instrument_hooks = _resolve_instrument_hooks(cd)
    device_sync = _has_tag_in_trace(extrace, OpTags.DEVICE_SYNC_OP)
    will_stage = not (cd.disable_jit_staging or device_sync or instrument_hooks)
    donate_buckets = (
        will_stage
        and sym_spec is not None
        and deopt_level < 1
        and on_nan_opt != "rerun-instrumented"
        and jaxex._donation_active()
    )
    static_plan, static_cert = _static_planner(
        extrace, sym_spec,
        donate=donate_buckets,
        rerun_capable=on_nan_opt == "rerun-instrumented",
    )
    phases["static_analysis"] = (timer_ns() - _phase_mark) / 1e9
    _phase_mark = timer_ns()  # codegen span starts after the planner

    # Per-op instrumentation (observability/instrument.py): bracket every
    # value-producing bsym with host pre/post hooks. Runs after claiming (so
    # records carry the executor) and before del_last_used (so dels land
    # after the hooks that consume the values). Instrumented entries execute
    # UNSTAGED — the hooks are host side effects XLA cannot stage. (Hooks
    # were resolved above, before the static planner, so the donation
    # decision already knows this entry won't stage.)
    if instrument_hooks:
        from thunder_tpu.observability.instrument import instrument_for_execution

        extrace = instrument_for_execution(extrace, instrument_hooks)
        computation_traces.append(extrace)

    extrace = del_last_used(extrace)
    computation_traces.append(extrace)

    plg_traces = [plg_trc]
    from thunder_tpu.extend import get_executor

    if cd.cache_option is CACHE_OPTIONS.SAME_INPUT:
        # SAME_INPUT semantics (reference: thunder/__init__.py:449 +
        # core/options.py:78-104): the user asserts every later call has
        # the same metadata AND values — guards are STRIPPED from the
        # prologue, so subsequent calls skip all checks. Unsafe by design;
        # differing inputs silently reuse the first specialization.
        check_ids = {
            PrimIDs.CHECK_TENSOR_SHAPE_AND_METADATA,
            PrimIDs.CHECK_NUMBER_TYPE_AND_VALUE,
            PrimIDs.CHECK_STRING_VALUE,
            PrimIDs.CHECK_LEN,
            PrimIDs.CHECK_KEYS,
            PrimIDs.CHECK_NONE,
        }
        stripped = from_trace(plg_trc)
        stripped.bound_symbols.extend(
            b for b in plg_trc.bound_symbols if b.sym.id not in check_ids
        )
        stripped.set_siginfo(plg_trc.siginfo)
        plg_trc = stripped
        plg_traces.append(plg_trc)

    plg_ex = transform_for_execution(plg_trc, (get_executor("python"),))
    plg_traces.append(plg_ex)

    _maybe_dump_trace(extrace)
    prologue_fn = plg_ex.python_callable()
    trace_callable = extrace.python_callable()
    # Everything between claiming and here: chaos/instrument passes,
    # del_last_used, the prologue claim, and source codegen + exec.
    phases["codegen"] = (timer_ns() - _phase_mark) / 1e9
    _phase_mark = timer_ns()

    needs_rng = bool(extrace.tags.get(RNG_TAG))
    if not will_stage:
        computation_fn = trace_callable
    elif sym_spec is not None:
        # Bucketed staging: padded input buffers are dispatch-owned
        # temporaries, donated to XLA off-CPU (executors/jaxex.py) — unless
        # the de-opt ladder disabled donation (level ≥ 1), or the on_nan
        # guard may re-run these exact buffers through the instrumented
        # trace (donated arrays are deleted after the staged run).
        # donate_buckets is THE donation predicate, computed once above for
        # the static planner — staging must not re-derive it (drift between
        # what was planned and what the executor does).
        computation_fn = jaxex.stage_bucketed(
            trace_callable, sorted(sym_spec.marks), donate=donate_buckets,
        )
        # Reconcile the trace's donation metadata with what the executor
        # actually stamped — by construction they agree (one predicate), but
        # the donation.* rules must read the executor's truth, not a plan.
        actual = getattr(computation_fn, "_thunder_donated_argnums", None)
        if actual is not None and not actual and extrace.tags.get("donated_inputs"):
            extrace.tags["donated_inputs"] = ()
    else:
        computation_fn = jax.jit(trace_callable)
    # jax.jit wrapper construction only — the XLA compile itself happens at
    # the entry's first run (the xla_compile phase recorded in fn_).
    phases["staging"] = (timer_ns() - _phase_mark) / 1e9

    torch_facing = any(bridge.is_torch_tensor(x) for x in tree_flatten((args, kwargs))[0])

    flat_call, call_treedef = tree_flatten((args, kwargs))
    on_nan = cd.compile_options.get("on_nan")
    entry = CacheEntry(
        prologue_fn=prologue_fn,
        computation_fn=computation_fn,
        epilogue_fn=_build_epilogue(input_mutations) if input_mutations else None,
        backward_fn=None,
        prologue_traces=plg_traces,
        computation_traces=computation_traces,
        backward_traces=[],
        torch_facing=torch_facing,
        needs_rng=needs_rng,
        value_guards=value_guards,
        sym_spec=sym_spec,
        treedef=call_treedef,
        leaf_meta=_leaf_meta(flat_call),
        on_nan=on_nan,
        claimed_extrace=claimed_extrace if on_nan else None,
    )
    entry.stats.trace_s = (timer_ns() - build_start) / 1e9
    entry.stats.degradation_level = deopt_level
    entry.stats.phases = phases
    entry.compile_id = compile_id
    if static_plan is not None:
        entry.stats.predicted_peak_bytes = int(static_plan.peak_bytes)
    entry.schedule_certificate = static_cert
    cs.trace_seconds += entry.stats.trace_s
    for phase in ("trace", "transforms", "claim", "static_analysis", "codegen",
                  "staging"):
        extra = compiled.extras.get(phase, {})
        if phase == "static_analysis" and static_plan is not None:
            extra = dict(
                predicted_peak_bytes=int(static_plan.peak_bytes),
                collective_sites=len(static_cert.sites) if static_cert else 0,
            )
        _record_compile_phase(compile_id, phase, phases.get(phase, 0.0), **extra)

    # Observability: compile-side metrics + the compile_end event carrying
    # the executor-claim breakdown and static collective traffic of the
    # final execution trace (executors/passes.py stamps them into tags).
    from thunder_tpu.observability import events

    claims = extrace.tags.get("claim_breakdown") or {}
    collective_bytes = int(extrace.tags.get("collective_bytes") or 0)
    if obsm.enabled():
        obsm.COMPILES.inc()
        if cs.compile_count > 1:
            obsm.RECOMPILES.inc()
        if sym_spec is not None:
            obsm.BUCKET_COMPILES.inc()
        obsm.COMPILE_MS.observe(entry.stats.trace_s * 1e3)
        for ex_name, n in claims.items():
            obsm.CLAIMED_BSYMS.inc(n, executor=ex_name)
        if collective_bytes:
            obsm.COLLECTIVE_BYTES.inc(collective_bytes)
    events.emit_compile_end(
        compile_id,
        getattr(cd.fn, "__name__", repr(cd.fn)),
        entry.stats.trace_s * 1e3,
        extrace,
        symbolic=sym_spec is not None,
        recompile=cs.compile_count > 1,
        staged=computation_fn is not trace_callable,
    )

    cs.last_traces = computation_traces
    cs.last_prologue_traces = plg_traces
    if cd.cache_option is not CACHE_OPTIONS.NO_CACHING:
        cs.cache_entries.append(entry)
    return entry


def _static_planner(extrace: TraceCtx, sym_spec, *, donate: bool, rerun_capable: bool):
    """The compile pipeline's static_analysis phase (ISSUE 10): stamp
    donation metadata on the claimed execution trace, plan its HBM liveness,
    and certify its collective schedule. Returns ``(MemoryPlan | None,
    ScheduleCertificate | None)`` — a planning failure degrades to None,
    never breaks a compile."""
    try:
        from thunder_tpu.analysis import liveness as live_mod
        from thunder_tpu.analysis import schedule as sched_mod

        donated_names: tuple = ()
        if donate and sym_spec is not None:
            args = [a for a in extrace.args if isinstance(a, TensorProxy)]
            donated_names = tuple(
                args[li].name for li in sorted(sym_spec.marks) if li < len(args)
            )
        extrace.tags["donated_inputs"] = donated_names
        if rerun_capable:
            extrace.tags["rerun_reads_inputs"] = True
        plan = live_mod.plan_liveness(
            extrace, donated=donated_names, include_rows=False
        )
        # Certify + stamp the per-axis collective order baseline; the
        # sched.uncertified-reorder rule diffs later passes against it, and
        # the watchdog attaches the axis order to timeout diagnoses.
        cert = sched_mod.stamp(extrace)
        return plan, cert
    except Exception:  # noqa: BLE001 — the planner is advisory, never fatal
        return None, None


def _resolve_instrument_hooks(cd: CompileData) -> tuple:
    """Hooks from jit(debug_watch=..., instrument=...), resolved ONCE per
    compiled function (not per entry) and stashed on cd: every cache entry
    of the function shares the same hook instances, so an OpTimer created
    from ``instrument="time"`` accumulates across shape specializations and
    ``instrument_reports`` sees all of it. Empty tuple (the common case)
    means no instrumentation pass runs and the entry stages whole under
    XLA — observability-off costs nothing."""
    hooks = getattr(cd, "_instrument_hooks", None)
    if hooks is not None:
        return hooks
    dw = cd.compile_options.get("debug_watch")
    ins = cd.compile_options.get("instrument")
    if not dw and ins is None:
        cd._instrument_hooks = ()
        return ()
    from thunder_tpu.observability.instrument import resolve_hooks

    hooks = resolve_hooks(dw, ins)
    cd._instrument_hooks = hooks
    return hooks


# Trace-dump-and-edit hook (reference: thunder/__init__.py:168-170 +
# trace.py:400-415 — write the final program to a file so a human can read
# or edit it; the canonical debugging tool is reading the generated Python).
_execution_callback_file = {"path": None}


def set_execution_callback_file(path: Optional[str]) -> None:
    _execution_callback_file["path"] = path


def _maybe_dump_trace(trc: TraceCtx) -> None:
    path = _execution_callback_file["path"]
    if path:
        with open(path, "a") as f:
            f.write(trc.python())
            f.write("\n\n")


_global_rng = {"seed": 0}


def seed(n: int) -> None:
    """Set the global RNG seed used for traces with random ops."""
    _global_rng["seed"] = n


def _next_key():
    import jax

    _global_rng["seed"] += 1
    return jax.random.PRNGKey(_global_rng["seed"])


def keyed_callable(claimed: TraceCtx) -> Callable:
    """``claimed.python_callable()`` for a front end that threads no RNG key of
    its own: a trace that draws random numbers takes its key last since
    ``pipeline.LOWER``, and gets the process's next one where it is called
    (under ``jax.jit``: where it is staged)."""
    fn = claimed.python_callable()
    if not claimed.tags.get(RNG_TAG):
        return fn
    return lambda *args: fn(*args, _next_key())


def _build_epilogue(muts: list) -> Callable:
    """Side-effect replay for input-mutating traced functions (reference:
    jit_ext.py `process_recorded_modifications:1302`).

    Called per execution with the caller's (args, kwargs), the prologue's
    flat tensor leaves, and the raw {"__out", "__muts"} computation output;
    applies each recorded mutation to the CALLER's objects and returns the
    user-visible output."""

    def navigate(args, kwargs, path):
        obj = args if path[0] == "args" else kwargs
        for k in path[1:]:
            obj = obj[k]
        return obj

    def build_value(spec, extras):
        tag, payload = spec
        if tag == "out":
            return extras[payload]
        if tag == "const":
            return payload
        if tag == "dict":
            return {k: build_value(v, extras) for k, v in payload.items()}
        if tag == "list":
            return [build_value(v, extras) for v in payload]
        return tuple(build_value(v, extras) for v in payload)  # "tuple"

    def epilogue(args, kwargs, flat_inps, raw_out):
        import numpy as np

        extras = raw_out["__muts"]
        for rec in muts:
            if rec[0] == "tensor":
                _, i, spec = rec
                target = flat_inps[i]
                val = build_value(spec, extras)
                if bridge.is_torch_tensor(target):
                    import torch

                    with torch.no_grad():
                        target.copy_(bridge.to_torch(val).to(target.dtype))
                elif isinstance(target, np.ndarray):
                    np.copyto(target, np.asarray(val).astype(target.dtype, copy=False))
                else:
                    # jax.Array inputs are immutable — nothing to write back;
                    # the functional value is still available via the output.
                    import warnings

                    warnings.warn(
                        "in-place update of an immutable (jax) input tensor "
                        "cannot be replayed onto the caller's array",
                        stacklevel=3,
                    )
            elif rec[0] == "set":
                _, path, key, spec = rec
                navigate(args, kwargs, path)[key] = build_value(spec, extras)
            elif rec[0] == "del":
                _, path, key = rec
                container = navigate(args, kwargs, path)
                container.pop(key, None)
            else:  # "resync": a list changed length/identity — rebuild it
                _, path, specs = rec
                container = navigate(args, kwargs, path)
                container[:] = [build_value(s, extras) for s in specs]
        return raw_out["__out"]

    return epilogue


def _prepare_inputs(entry: CacheEntry, flat_inps) -> tuple[list, Optional[dict]]:
    """(jax inputs — bucket-padded for symbolic entries, true extents) for an
    entry. Shared by value-guard evaluation and execution so a value-guarded
    dispatch converts/pads each leaf exactly once."""
    inps = [bridge.to_jax(x) for x in flat_inps]
    true_extents = None
    if entry.sym_spec is not None:
        true_extents = entry.sym_spec.true_extents(flat_inps)
        inps = jaxex.pad_to_bucket(inps, entry.sym_spec)
    return inps, true_extents


def _run_entry(cs: CompileStats, entry: CacheEntry, flat_inps: tuple, prepared=None) -> Any:
    """One run of a cache entry: inputs prepared (span
    ``thunder_tpu.call.prepare``), the program called (span
    ``thunder_tpu.call.launch``, between ``cs.last_trace_launch_start`` and
    ``_stop``), then its outputs cropped, checked and converted."""
    with TraceAnnotation("thunder_tpu.call.prepare"):
        inps, true_extents = prepared if prepared is not None else _prepare_inputs(entry, flat_inps)
        if entry.sym_spec is not None:
            import numpy as np

            # Runtime true extents feed the reduction masks (transforms/padmask.py)
            # — and the de-opt ladder's L3 exact-shape peak prediction for THIS
            # call, should this dispatch OOM (resilience/deopt.py).
            entry.last_true_extents = true_extents
            inps = inps + [
                np.asarray(true_extents[cid], np.int32) for cid in entry.sym_spec.mask_classes
            ]
        if entry.needs_rng:
            inps = inps + [_next_key()]
        if getattr(entry, "_hlo_audit_pending", False):
            # First run of a fresh entry: snapshot the staged callable's input
            # avals so the post-compile HLO auditor (_maybe_hlo_audit) can
            # re-lower without holding references to (possibly donated) buffers.
            entry._hlo_audit_pending = False
            try:
                import jax

                entry.hlo_audit_avals = tuple(
                    jax.ShapeDtypeStruct(tuple(x.shape), x.dtype) for x in inps
                )
            except Exception:  # noqa: BLE001 — advisory capture only
                entry.hlo_audit_avals = None
        if chaos_mod.enabled():
            # Chaos seams: injected device OOM (recovered by the de-opt ladder)
            # and the collective-straggler delay. One contextvar probe when
            # chaos is inactive.
            trc = entry.computation_traces[-1] if entry.computation_traces else None
            chaos_mod.run_seam(
                has_collectives=bool(
                    trc is not None and int(trc.tags.get("collective_bytes") or 0)
                ),
                deopt_level=entry.stats.degradation_level,
            )
    cs.last_trace_launch_start = timer_ns()
    with TraceAnnotation("thunder_tpu.call.launch"):
        if watchdog_mod.active_timeout() is not None:
            # Collective watchdog: a dispatch whose trace contains
            # dist_prims collectives runs under the configured timeout, so a
            # peer that stops participating raises a typed CollectiveTimeoutError
            # naming the pending trace lines instead of hanging this host
            # forever. One dict probe per call when no timeout is configured.
            if entry.collective_lines is None:
                from thunder_tpu.distributed import prims as dist_prims

                trc = entry.computation_traces[-1] if entry.computation_traces else None
                entry.collective_lines = tuple(dist_prims.collective_trace_lines(trc))
            if entry.collective_lines:
                cert = entry.schedule_certificate
                out = watchdog_mod.guard_call(
                    entry.computation_fn, tuple(inps),
                    fn_name=getattr(entry.computation_fn, "__name__", "computation"),
                    trace_lines=entry.collective_lines,
                    schedule=cert.axis_labels() if cert is not None else None,
                )
            else:
                out = entry.computation_fn(*inps)
        else:
            out = entry.computation_fn(*inps)
    cs.last_trace_launch_stop = timer_ns()
    if entry.sym_spec is not None:
        out = jaxex.crop_to_extents(out, entry.sym_spec, true_extents)
    if entry.on_nan is not None and not deopt_mod.outputs_finite(out):
        # Post-step isfinite guard (jit(on_nan=...)), checked on the CROPPED
        # output — padding lanes of a bucketed entry may legitimately hold
        # inf/NaN (e.g. 1/0 on zero-padded rows) that the crop discards.
        # Attribution re-runs the SAME (padded) inputs instrumented.
        deopt_mod.handle_nonfinite(entry, inps, entry.on_nan)
    if entry.torch_facing:
        import jax

        out = tree_map(lambda x: bridge.to_torch(x) if isinstance(x, jax.Array) else x, out)
    return out


def _hlo_audit_enabled() -> bool:
    import os

    return os.environ.get("THUNDER_TPU_HLO_AUDIT", "1").strip().lower() not in (
        "0", "false", "off",
    )


def _bucket_pad_fractions(entry: CacheEntry) -> dict:
    """Bucket class label → padded-away fraction (1 − true/padded extent) of
    a symbolic entry's last dispatch — the ``hlo.padding-waste`` rule input."""
    spec = entry.sym_spec
    true_ext = getattr(entry, "last_true_extents", None)
    if spec is None or not true_ext:
        return {}
    out: dict = {}
    for cid, (li, d, _lo, hi) in spec.classes.items():
        t = true_ext.get(cid)
        if t is None or hi <= 0:
            continue
        out[f"leaf{li}.dim{d}"] = round(max(0.0, 1.0 - t / hi), 4)
    return out


def _maybe_hlo_audit(entry: CacheEntry, log=None) -> None:
    """Post-``xla_compile`` compile phase: audit the entry's compiled HLO
    (analysis/hlo_audit.py) — partitioner-inserted collectives, layout
    copies, host transfers, static exposed-wire — and attach the report to
    the entry and the extrace tags (``hlo_audit``), where the advisory
    ``hlo.*`` verifier rules read it. Advisory-safe by contract: any
    auditor failure emits a ``sharp_edge`` and never breaks the compile;
    ``THUNDER_TPU_HLO_AUDIT=0`` is the kill switch."""
    import time as _time

    avals = getattr(entry, "hlo_audit_avals", None)
    jfn = entry.computation_fn
    if not avals or jfn is None or not hasattr(jfn, "lower"):
        return
    t0 = _time.perf_counter()
    try:
        from thunder_tpu.analysis import hlo_audit as _hlo_audit_mod

        text = jfn.lower(*avals).compile().as_text()
        acquire_s = _time.perf_counter() - t0
        report = _hlo_audit_mod.audit_hlo(text, pad_fractions=_bucket_pad_fractions(entry))
        total_s = _time.perf_counter() - t0
        report.audit_s = total_s
        entry.hlo_audit = report
        if entry.computation_traces:
            entry.computation_traces[-1].tags["hlo_audit"] = report
        entry.stats.phases["hlo_audit"] = total_s
        # Optional fields by PRESENCE (PR 10 discipline): an absent field
        # means the audit had nothing to say there, not zero.
        extra: dict = dict(
            hlo_ops=report.n_ops,
            hlo_acquire_s=round(acquire_s, 6),
            hlo_analyze_s=round(total_s - acquire_s, 6),
        )
        if report.sites:
            extra["hlo_collectives"] = len(report.sites)
            extra["hlo_inserted_collectives"] = report.inserted_collectives
            extra["hlo_exposed_pct"] = round(report.exposed_pct, 2)
        if report.host_transfers:
            extra["hlo_host_transfers"] = report.host_transfers
        _record_compile_phase(entry.compile_id, "hlo_audit", total_s, log=log, **extra)
    except Exception as e:  # noqa: BLE001 — the auditor must never break a compile
        sharp_edge(f"hlo_audit failed (advisory): {type(e).__name__}: {e}")


# =============================================================================
# Dispatch: O(1) fast path + symbolic-values (bucketed) compilation
# =============================================================================


def _leaf_meta(flat: list) -> tuple:
    """Hashable per-leaf metadata covering everything the prologue guards:
    tensor (shape, dtype, device kind, requires_grad, framework), number
    type+value, string value, None. Opaque objects key by type only — the
    prologue cannot guard them either (sharp edge)."""
    parts = []
    for x in flat:
        if bridge.is_concrete_tensor(x):
            shape, dev, dt, rg = bridge.tensor_metadata(x)
            parts.append(
                ("T", tuple(int(s) for s in shape), str(dt), str(dev).split(":")[0],
                 rg, bridge.framework_of(x))
            )
        elif isinstance(x, (bool, int, float, complex, str)) or x is None:
            parts.append((type(x).__name__, x))
        else:
            parts.append(("O", type(x).__name__))
    return tuple(parts)


_FAST_CACHE_MAX = 4096


def _probe_entries(cs: CompileStats, args: tuple, kwargs: dict):
    """Full prologue scan, newest entries first (the slow path): each probe
    executes the candidate's prologue; GuardFailure is the controlled miss
    signal (reference: thunder/__init__.py:409-447). Returns (entry,
    flat_inps, prepared) — ``prepared`` is the converted/padded input set
    when value guards forced preparing it (reused by _run_entry)."""
    from thunder_tpu.core.concrete import check_value_guards

    for entry in reversed(cs.cache_entries):
        cs.prologue_runs += 1
        entry.stats.prologue_runs += 1
        try:
            flat_inps = entry.prologue_fn(*args, **kwargs)
        except GuardFailure:
            # Controlled signal from a CHECK_* prim: this entry's guards
            # don't match → probe the next entry. Any other exception is a
            # genuine bug (in guard code or user input) and propagates.
            entry.stats.guard_fails += 1
            continue
        prepared = None
        if entry.value_guards:
            # The guard subprograms were staged on the (padded) trace shapes.
            prepared = _prepare_inputs(entry, flat_inps)
            if not check_value_guards(entry.value_guards, prepared[0]):
                entry.stats.guard_fails += 1
                continue
        return entry, flat_inps, prepared
    return None, None, None


def _symbolic_spec_for_call(cd: CompileData, cs: CompileStats, args: tuple, kwargs: dict):
    """Which dims to lift symbolic for THIS compile, or None for an exact
    entry. Explicit ``symbolic_dims`` marks apply from the first call;
    ``"auto"`` (the default) marks the dims observed VARYING against a cached
    entry of the same shape class — parameters never vary, so they are never
    padded, while batch/sequence dims self-discover."""
    from thunder_tpu.core.bucketing import make_symbolic_spec

    flat, treedef = tree_flatten((args, kwargs))
    tensor_pos = [i for i, x in enumerate(flat) if bridge.is_concrete_tensor(x)]
    shapes = {li: tuple(int(s) for s in flat[i].shape) for li, i in enumerate(tensor_pos)}

    explicit = cd.compile_options.get("symbolic_dims", "auto")
    if explicit is None or explicit == "auto":
        marks_dims = _marks_from_variation(cs, _leaf_meta(flat), treedef)
    elif explicit == "all":
        marks_dims = {li: tuple(range(len(s))) for li, s in shapes.items()}
    elif isinstance(explicit, dict):
        marks_dims = {int(li): tuple(ds) for li, ds in explicit.items()}
    elif isinstance(explicit, (tuple, list)):
        marks_dims = {
            li: tuple(d for d in explicit if d < len(s)) for li, s in shapes.items()
        }
        marks_dims = {li: ds for li, ds in marks_dims.items() if ds}
    else:
        raise ValueError(
            f"symbolic_dims: expected 'auto', 'all', a dict of leaf->dims, or a "
            f"dim tuple; got {explicit!r}"
        )
    marks_dims = {li: ds for li, ds in marks_dims.items() if ds}
    if not marks_dims:
        return None
    # jit() resolves the policy whenever cache_option is SYMBOLIC_VALUES —
    # the only path that reaches this function.
    return make_symbolic_spec(marks_dims, shapes, cd.compile_options["_bucket_policy"])


def _marks_from_variation(cs: CompileStats, cur_meta: tuple, treedef) -> dict:
    """Compare the call's leaf metadata against cached entries of the same
    shape class; the dims whose extents differ (plus the entry's existing
    symbolic dims) become the new entry's marks."""
    for entry in reversed(cs.cache_entries):
        if entry.treedef != treedef or len(entry.leaf_meta) != len(cur_meta):
            continue
        entry_marks = entry.sym_spec.marks if entry.sym_spec is not None else {}
        marks: dict[int, tuple] = {}
        li = -1
        ok = True
        for cm, em in zip(cur_meta, entry.leaf_meta):
            if cm[0] == "T" or em[0] == "T":
                if cm[0] != "T" or em[0] != "T":
                    ok = False
                    break
                li += 1
                if cm[2:] != em[2:] or len(cm[1]) != len(em[1]):
                    ok = False  # dtype/device/rank class differs: not this entry
                    break
                inherited = set(entry_marks.get(li, {}).keys())
                diff = {d for d in range(len(cm[1])) if cm[1][d] != em[1][d]}
                dims = inherited | diff
                if dims:
                    marks[li] = tuple(sorted(dims))
            elif cm != em:
                ok = False
                break
        if ok and marks:
            return marks
    return {}


def _pad_example(args: tuple, kwargs: dict, sym_spec) -> tuple[tuple, dict]:
    """Zero-pad the example inputs up to the spec's bucket ceilings — the
    shapes the symbolic trace is acquired on."""
    flat, treedef = tree_flatten((args, kwargs))
    tensor_pos = [i for i, x in enumerate(flat) if bridge.is_concrete_tensor(x)]
    for li, dims in sym_spec.marks.items():
        i = tensor_pos[li]
        flat[i] = _pad_concrete(flat[i], {d: hi for d, (_lo, hi, _cid) in dims.items()})
    return tree_unflatten(treedef, flat)


def _pad_concrete(x: Any, targets: dict):
    widths = [(0, 0)] * len(x.shape)
    padded = False
    for d, t in targets.items():
        delta = int(t) - int(x.shape[d])
        if delta > 0:
            widths[d] = (0, delta)
            padded = True
    if not padded:
        return x
    if bridge.is_torch_tensor(x):
        import torch

        for d, (_z, delta) in enumerate(widths):
            if delta:
                pad_shape = list(x.shape)
                pad_shape[d] = delta
                x = torch.cat(
                    [x, torch.zeros(pad_shape, dtype=x.dtype, device=x.device)], dim=d
                )
        return x
    import numpy as np

    if isinstance(x, np.ndarray):
        return np.pad(x, widths)
    import jax.numpy as jnp

    return jnp.pad(x, widths)


def _sum_phases(entries) -> dict:
    out: dict[str, float] = {}
    for e in entries:
        for phase, v in e.stats.phases.items():
            if isinstance(v, (int, float)):
                out[phase] = out.get(phase, 0.0) + v
    return {k: round(v, 6) for k, v in sorted(out.items())}


# Live jitted functions, weakly held — the ops plane's /debug/state reads
# each one's cache/compile summary without the operator having to hold a
# handle (observability/opsplane.py). WeakSet: registration must never be
# the thing keeping a dropped function's cache entries alive.
import weakref as _weakref

_live_functions: "_weakref.WeakSet" = _weakref.WeakSet()


def live_function_state() -> list[dict]:
    """Per-function cache/compile summaries across every live jitted
    function — :func:`cache_info` trimmed to what an operator scans (entry
    lists collapsed to counts + per-entry de-opt levels)."""
    out = []
    for f in list(_live_functions):
        try:
            info = cache_info(f)
        except Exception:
            continue
        entries = info.pop("entries", [])
        info["n_entries"] = len(entries)
        info["entry_degradation_levels"] = [
            e.get("degradation_level", 0) for e in entries
        ]
        info["fn"] = getattr(f, "__name__", "?")
        info["trace_seconds"] = round(info.get("trace_seconds") or 0.0, 4)
        info["first_run_seconds"] = round(info.get("first_run_seconds") or 0.0, 4)
        out.append(info)
    return sorted(out, key=lambda i: str(i.get("fn")))


def cache_info(fn: Callable) -> dict:
    """Cache observability for a thunder_tpu-compiled function: aggregate and
    per-entry hit/miss/recompile counters plus cumulative trace/first-run
    seconds (ISSUE 2; printed by ``examine.lint``'s summary)."""
    cs = _get_cs(fn)
    cd = getattr(fn, "_lc_cd", None)
    return {
        "cache_option": cd.cache_option.name.lower() if cd is not None else None,
        "calls": cs.calls,
        "hits": cs.cache_hits,
        "misses": cs.cache_misses,
        "fast_hits": cs.fast_hits,
        "slow_hits": cs.slow_hits,
        "prologue_runs": cs.prologue_runs,
        "compiles": cs.compile_count,
        "recompiles": cs.recompile_count,
        "trace_seconds": cs.trace_seconds,
        "first_run_seconds": cs.first_run_seconds,
        "cache_lookup_us_total": cs.cache_lookup_ns / 1e3,
        # Compile-phase rollup across entries (seconds per phase): the
        # decomposition of trace_seconds + first_run_seconds the
        # compile_phase events record per compile (docs/observability.md).
        "compile_phase_seconds": _sum_phases(cs.cache_entries),
        # De-opt ladder position new compiles use (per-entry levels are in
        # each entry's stats below) — resilience/deopt.py.
        "degradation_level": deopt_mod.current_level(cd) if cd is not None else 0,
        "entries": [
            dict(
                index=i,
                symbolic=(e.sym_spec is not None),
                buckets=(e.sym_spec.describe() if e.sym_spec is not None else "exact"),
                **e.stats.as_dict(),
            )
            for i, e in enumerate(cs.cache_entries)
        ],
    }


# =============================================================================
# jit()
# =============================================================================


def _ensure_runtime() -> None:
    """Configure JAX for torch-faithful dtype semantics, once, at first use.

    ``jax_enable_x64`` is required so int64 indices and requested float64
    round-trip exactly (the hot compute path is explicitly bf16/f32 in
    traces, so this costs nothing on TPU). Done lazily here — not at import
    — so merely importing thunder_tpu does not mutate an unrelated host
    process's JAX configuration.
    """
    import jax

    if not jax.config.jax_enable_x64:
        jax.config.update("jax_enable_x64", True)

    # Tap jax's compilation-cache monitoring events so first-run compile
    # spans can say "hit" (deserialize) vs "miss" (real backend compile).
    _install_jax_cache_listener()

    # Ops plane autostart (ISSUE 15): THUNDER_TPU_OPS_PORT arms the live
    # endpoints + flight recorder with zero code changes — the scheduler
    # exports one port per process and the fleet is scrapeable. One env
    # probe here; nothing is imported (let alone served) without it.
    import os as _os

    if _os.environ.get("THUNDER_TPU_OPS_PORT", "").strip():
        from thunder_tpu.observability import opsplane as _opsplane

        _opsplane.maybe_autostart()

    # Persistent XLA compilation cache (reference analogue: nvFuser's
    # descriptor-keyed compiled-fusion cache, SURVEY.md §2.2 — here the
    # cache survives processes, so warm-start recompiles of the same
    # program are file reads, not 80-second XLA runs). Opt out with
    # THUNDER_TPU_NO_COMPILE_CACHE=1. The cache is placed from outside with
    # jax's own JAX_COMPILATION_CACHE_DIR: jax carries that into its config
    # before this runs, and then nothing is set here (the same holds for a
    # directory set programmatically, and the JAX_PERSISTENT_CACHE_* knobs).
    # Unset, the cache lives in the checkout, at a path that does not move.
    # A directory that cannot be made is an error.
    import os

    if not os.environ.get("THUNDER_TPU_NO_COMPILE_CACHE"):
        from thunder_tpu.resilience import compile_cache

        cache_dir = jax.config.jax_compilation_cache_dir
        if not cache_dir:
            cache_dir = compile_cache.default_cache_dir()
            os.makedirs(cache_dir, exist_ok=True)
            jax.config.update("jax_compilation_cache_dir", cache_dir)
            _set_unless_user_configured(
                jax, "jax_persistent_cache_min_compile_time_secs", 1.0
            )
            _set_unless_user_configured(
                jax, "jax_persistent_cache_min_entry_size_bytes", 0
            )
        if _cache_dir_logged["dir"] != cache_dir:
            # First sight of this cache dir in the process: the chaos
            # cache_corrupt seam may truncate an entry here (no-op unless
            # armed), then the sweep removes corrupted/truncated entries
            # (torn writes from a crashed or disk-full predecessor) so a
            # poisoned entry recompiles instead of crashing the load
            # (resilience/compile_cache.py).
            chaos_mod.corrupt_cache_seam(cache_dir)
            compile_cache.sweep_corrupt_entries(cache_dir)
        _log_cache_dir_once(cache_dir)


def _set_unless_user_configured(jax_mod, name: str, value) -> None:
    """Apply our persistent-cache tuning only when the user has not already
    configured the knob — via the env var jax reads, or programmatically.
    The values we set equal jax's own defaults, so a current value that
    differs from ours can only mean the user changed it: respect it."""
    import os

    if os.environ.get(name.upper()) is not None:
        return
    if getattr(jax_mod.config, name) != value:
        return
    jax_mod.config.update(name, value)


_cache_dir_logged = {"dir": None}


def _log_cache_dir_once(cache_dir: str) -> None:
    if _cache_dir_logged["dir"] == cache_dir:
        return
    _cache_dir_logged["dir"] = cache_dir
    import logging

    logging.getLogger("thunder_tpu").info("persistent XLA compile cache: %s", cache_dir)


def jit(
    fn: Optional[Callable] = None,
    *,
    executors: Optional[Sequence] = None,
    cache: str | CACHE_OPTIONS = CACHE_OPTIONS.CONSTANT_VALUES,
    sharp_edges: str | SHARP_EDGES_OPTIONS = SHARP_EDGES_OPTIONS.ALLOW,
    disable_jit_staging: bool = False,
    debug_checks: Optional[bool] = None,
    events: Optional[str] = None,
    debug_watch: Optional[str] = None,
    instrument: Any = None,
    chaos: Any = None,
    on_nan: Optional[str] = None,
    **compile_options,
) -> Callable:
    """Compile ``fn`` for TPU execution (reference: thunder/__init__.py `jit:299`).

    ``fn`` may be written against thunder_tpu's torch-mirror language, be a
    real ``torch.nn.Module``/torch function (acquired via the torch
    frontend), or operate on jax/numpy arrays directly.

    ``debug_checks=True`` runs the static trace verifier (thunder_tpu/analysis)
    after every transform pass, raising ``TraceVerificationError`` attributed
    to the pass that broke an invariant; ``False`` disables it; ``None``
    (default) defers to the ``THUNDER_TPU_CHECKS`` environment variable.

    ``cache="symbolic values"`` enables shape-polymorphic caching: marked
    tensor dims are lifted into bucket guards (``lo < d <= hi``) instead of
    exact extents, inputs are zero-padded up to the bucket ceiling at
    dispatch, reductions over padded dims are masked against the runtime
    true extents, and outputs are cropped back — one trace + one XLA compile
    per bucket. Options: ``symbolic_dims`` ("auto" = mark dims observed
    varying, "all", a ``{tensor_leaf_index: (dims...)}`` dict, or a dim
    tuple) and ``buckets`` (e.g. ``{"batch": "pow2", "seq": 128}``; also the
    ``THUNDER_TPU_BUCKETS`` env var). See docs/caching.md.

    Observability (docs/observability.md):

    - ``events="<path>"`` writes this function's compile/cache/bucket events
      as JSONL to ``path`` (overriding the process-wide ``THUNDER_TPU_EVENTS``
      log for this function);
    - ``debug_watch="nan"`` (or ``"inf"``/``"nan+inf"``) instruments every
      bound symbol and raises :class:`~thunder_tpu.observability.instrument.
      NaNWatchError` — with the offending BoundSymbol name, generated trace
      line, and pass provenance — the moment an output turns non-finite;
    - ``instrument`` takes ``"time"``, ``"memory"``, a custom
      ``InstrumentationHook``, a bare ``fn(rec, outputs)`` callable, or a
      list of those. Instrumented entries run unstaged (op-by-op); with
      neither option the entry stages whole under XLA as usual.

    Resilience (docs/robustness.md):

    - ``chaos`` takes a chaos spec string (or ``ChaosConfig``) activating
      deterministic fault injection for this function's compiles and runs —
      the programmatic spelling of ``THUNDER_TPU_CHAOS``;
    - ``on_nan`` arms a cheap post-step isfinite guard over the outputs:
      ``"raise"`` raises :class:`~thunder_tpu.resilience.NonFiniteOutputError`,
      ``"rerun-instrumented"`` first re-runs the failing step once under a
      NaN watcher so the error names the producing op, ``"warn"`` warns and
      returns the result.
    """
    if fn is None:
        return functools.partial(
            jit,
            executors=executors,
            cache=cache,
            sharp_edges=sharp_edges,
            disable_jit_staging=disable_jit_staging,
            debug_checks=debug_checks,
            events=events,
            debug_watch=debug_watch,
            instrument=instrument,
            chaos=chaos,
            on_nan=on_nan,
            **compile_options,
        )

    _ensure_runtime()

    # autocast option → a trace transform running before grad/claiming
    # (reference: thunder/__init__.py:543 applies autocast pre-split).
    ac = compile_options.pop("autocast", None)
    if ac:
        from thunder_tpu.transforms.autocast import autocast as _ac_transform

        ac_dtype = dtypes.to_dtype(ac) if not isinstance(ac, bool) else dtypes.bfloat16
        tts = tuple(compile_options.get("_trace_transforms", ()))
        compile_options["_trace_transforms"] = (lambda trc: _ac_transform(trc, ac_dtype),) + tts

    # torch nn.Module → ThunderModule wrapper (the torch frontend).
    _torch = None
    try:
        import torch as _torch
    except ImportError:
        pass
    if _torch is not None and isinstance(fn, _torch.nn.Module):
        if debug_watch or instrument is not None:
            raise NotImplementedError(
                "debug_watch/instrument are not yet supported on the torch "
                "nn.Module frontend — jit the functional forward instead"
            )
        if chaos is not None or on_nan is not None:
            raise NotImplementedError(
                "chaos/on_nan are not yet supported on the torch nn.Module "
                "frontend — use THUNDER_TPU_CHAOS for process-wide chaos, or "
                "jit the functional forward instead"
            )
        from thunder_tpu.frontend.module import thunder_module

        return thunder_module(
            fn, executors=executors, cache=cache, sharp_edges=sharp_edges,
            disable_jit_staging=disable_jit_staging, debug_checks=debug_checks,
            events=events, **compile_options
        )

    cache_option = resolve_cache_option(cache)
    if cache_option is CACHE_OPTIONS.SYMBOLIC_VALUES:
        # Resolve the shape-bucketing policy once, at jit() time: defaults
        # (pow2 batch, 128-multiple seq) <- THUNDER_TPU_BUCKETS <- buckets=.
        from thunder_tpu.core.bucketing import BucketPolicy

        compile_options["_bucket_policy"] = BucketPolicy.resolve(
            compile_options.pop("buckets", None)
        )
    else:
        compile_options.pop("buckets", None)

    cd = CompileData(
        fn=fn,
        executors_list=resolve_executors(executors),
        cache_option=cache_option,
        sharp_edges=resolve_sharp_edges_option(sharp_edges),
        disable_jit_staging=disable_jit_staging,
        compile_options=dict(
            compile_options, debug_checks=debug_checks,
            debug_watch=debug_watch, instrument=instrument,
            on_nan=deopt_mod.resolve_on_nan(on_nan),
        ),
    )
    # Per-function chaos config (resilience/chaos.py): parsed once here,
    # activated around every dispatch of this function.
    cd._chaos = chaos_mod.resolve(chaos)
    if events:
        cd._event_log = obs_events.log_for_path(events)
    cs = CompileStats()

    fn_name = getattr(fn, "__name__", repr(fn))

    @functools.wraps(fn)
    def fn_(*args, **kwargs):
        start = timer_ns()
        with TraceAnnotation("thunder_tpu.call"):
            log = getattr(cd, "_event_log", None)
            if cd._chaos is None and log is None:
                result, record = _dispatch(args, kwargs, start)
            else:
                import contextlib

                # The function's own event log and chaos config cover the WHOLE
                # dispatch (not just the compile scope): fault injections, demotions,
                # and de-opt events fire at run time and must land in the same log
                # their compile events do.
                with contextlib.ExitStack() as stack:
                    if log is not None:
                        stack.enter_context(obs_events.event_scope(log))
                    if cd._chaos is not None:
                        stack.enter_context(chaos_mod.chaos_scope(cd._chaos))
                    result, record = _dispatch(args, kwargs, timer_ns())
        cs.last_trace_host_start, cs.last_trace_host_stop = start, timer_ns()
        record["at"] = cs.last_trace_host_stop / 1e9
        record["s"] = (cs.last_trace_host_stop - start) / 1e9
        _dispatch_records.append(record)
        if record["hit"] != "miss" and obsm.enabled():
            obsm.DISPATCH_US.observe((cs.last_trace_host_stop - start) / 1e3)
        return result

    def _dispatch(args: tuple, kwargs: dict, lookup_start: int):
        """(the call's result, its record in ``dispatch_phases()`` but for
        the call's own bounds, which ``fn_`` adds)."""
        from thunder_tpu.core.concrete import check_value_guards

        cs.calls += 1
        cs.last_trace_cache_start = lookup_start
        co = cd.cache_option
        entry = None
        flat_inps = None
        prepared = None
        key = None
        hit_kind = "miss"
        with TraceAnnotation("thunder_tpu.call.lookup"):
            if co in (CACHE_OPTIONS.CONSTANT_VALUES, CACHE_OPTIONS.SYMBOLIC_VALUES):
                flat, treedef = tree_flatten((args, kwargs))
                key = (treedef, _leaf_meta(flat))

            if co is CACHE_OPTIONS.SAME_INPUT and cs.cache_entries:
                # SAME_INPUT short-circuits to the NEWEST entry: the user asserts
                # every call repeats the first one's metadata AND values, so no
                # probing (and no value-guard re-evaluation) happens — previously
                # a value-guard miss could compile a second entry and the reversed
                # scan could then bounce between specializations.
                entry = cs.cache_entries[-1]
                cs.prologue_runs += 1
                entry.stats.prologue_runs += 1
                flat_inps = entry.prologue_fn(*args, **kwargs)
                hit_kind = "same_input"
            elif key is not None and cs.cache_entries:
                # Two-tier dispatch. Tier 1: O(1) key hit — (tree structure, per
                # leaf rank/shape/dtype/device/value metadata) → entry, learned on
                # the first slow hit; no prologue executes on the warm path.
                cand = cs.fast_cache.get(key)
                if cand is not None:
                    leaves = [x for x in flat if bridge.is_concrete_tensor(x)]
                    guards_ok = True
                    if cand.value_guards:
                        prepared = _prepare_inputs(cand, leaves)
                        guards_ok = check_value_guards(cand.value_guards, prepared[0])
                    if guards_ok:
                        entry = cand
                        flat_inps = leaves
                        cs.fast_hits += 1
                        entry.stats.fast_hits += 1
                        hit_kind = "fast"
                    else:
                        prepared = None
                if entry is None:
                    # Tier 2: full prologue scan, newest first; a hit teaches the
                    # fast path this key.
                    entry, flat_inps, prepared = _probe_entries(cs, args, kwargs)
                    if entry is not None:
                        cs.slow_hits += 1
                        hit_kind = "slow"
                        if len(cs.fast_cache) > _FAST_CACHE_MAX:
                            cs.fast_cache.clear()
                        cs.fast_cache[key] = entry
        cs.last_trace_cache_stop = timer_ns()
        lookup_ns = cs.last_trace_cache_stop - cs.last_trace_cache_start
        cs.cache_lookup_ns += lookup_ns

        def record(hit: str, prepare_start: int) -> dict:
            return {"fn": fn_name, "hit": hit, "lookup_s": lookup_ns / 1e9,
                    "prepare_s": (cs.last_trace_launch_start - prepare_start) / 1e9,
                    "launch_s": (cs.last_trace_launch_stop - cs.last_trace_launch_start) / 1e9}

        if entry is not None:
            cs.cache_hits += 1
            entry.stats.hits += 1
            try:
                result = _run_entry(cs, entry, flat_inps, prepared)
            except Exception as e:
                # Resilience (resilience/deopt.py): a kernel/OOM failure on a
                # warm entry evicts it, quarantines or de-opts, and falls
                # through to the recompile path below. Anything unrecognized
                # propagates untouched.
                if not deopt_mod.handle_run_failure(e, cd, cs, entry, 0):
                    # Unhandled dispatch fault: the flight ring's preceding
                    # context dumps before the raise unwinds (ISSUE 15;
                    # no-op one-probe when the ops plane is off).
                    obs_events.flight_dump("dispatch_fault")
                    raise
                entry = None
                # Re-account the call as a miss (it recompiles below).
                cs.cache_hits -= 1
            if entry is not None:
                if entry.epilogue_fn is not None:
                    result = entry.epilogue_fn(args, kwargs, flat_inps, result)
                if obsm.enabled():
                    # Single flag check on the warm path when metrics are off
                    # (budgets: <1% off, <5% on).
                    obsm.CACHE_HITS.inc(kind=hit_kind)
                    obsm.CACHE_LOOKUP_US.observe(lookup_ns / 1e3)
                return result, record(hit_kind, cs.last_trace_cache_stop)

        cs.cache_misses += 1
        if obsm.enabled():
            obsm.CACHE_MISSES.inc()
        # emit_event: fn_ already routed the per-function log (event_scope),
        # so the active log is the right sink — and the ops-plane taps see
        # the miss even with no log configured (ISSUE 15).
        obs_events.emit_event("cache_miss", fn=fn_name, call=cs.calls)
        # Compile + first run under the recovery driver: a failure that
        # classifies as a kernel fault demotes the claimed executor and
        # re-claims; a compile failure/OOM climbs the de-opt ladder; both
        # retry bounded with backoff. Unrecognized failures propagate on the
        # first throw.
        compile_start = timer_ns()
        with TraceAnnotation("thunder_tpu.call.compile"):
            attempt = 0
            while True:
                try:
                    entry = _compile_entry(cd, cs, args, kwargs)
                except Exception as e:
                    if deopt_mod.handle_compile_failure(e, cd, cs, attempt):
                        attempt += 1
                        continue
                    obs_events.flight_dump("dispatch_fault")
                    raise
                if key is not None:
                    if len(cs.fast_cache) > _FAST_CACHE_MAX:
                        cs.fast_cache.clear()
                    cs.fast_cache[key] = entry
                entry.stats.hits += 1
                cs.prologue_runs += 1
                entry.stats.prologue_runs += 1
                flat_inps = entry.prologue_fn(*args, **kwargs)
                # Aval capture is unconditional (one-time, bytes-cheap) so
                # examine.hlo_report can audit on demand even when the
                # compile-time phase is disabled; only the audit itself gates
                # on THUNDER_TPU_HLO_AUDIT.
                entry._hlo_audit_pending = True
                jax_compile0 = _jax_cache_counts()
                run_start = timer_ns()
                try:
                    result = _run_entry(cs, entry, flat_inps)
                except Exception as e:
                    if deopt_mod.handle_run_failure(e, cd, cs, entry, attempt):
                        if key is not None:
                            cs.fast_cache.clear()
                        attempt += 1
                        continue
                    obs_events.flight_dump("dispatch_fault")
                    raise
                break
        run_stop = timer_ns()
        entry.stats.first_run_s = (run_stop - run_start) / 1e9
        cs.first_run_seconds += entry.stats.first_run_s
        # Persistent-XLA-cache verdict of the first run: "hit" means those
        # seconds were a deserialize, "miss" a real backend compile — the
        # phase split that tells a cold-start regression from a cache-key
        # change (docs/observability.md, compile-phase spans). The backend-
        # compile and cache-retrieval sub-spans come from jax's own
        # monitoring durations, so the wall total decomposes further.
        jax_compile1 = _jax_cache_counts()
        cache_verdict = None
        if jax_compile1["misses"] > jax_compile0["misses"]:
            cache_verdict = "miss"
        elif jax_compile1["hits"] > jax_compile0["hits"]:
            cache_verdict = "hit"
        entry.stats.phases["xla_compile"] = entry.stats.first_run_s
        if cache_verdict:
            entry.stats.phases["persistent_cache"] = cache_verdict
        _entry_log = getattr(cd, "_event_log", None)
        for sub, key in (("xla_backend_compile", "backend_compile_s"),
                         ("persistent_cache_get", "cache_get_s")):
            delta = jax_compile1[key] - jax_compile0[key]
            if delta > 0.0:
                entry.stats.phases[sub] = delta
                _record_compile_phase(entry.compile_id, sub, delta, log=_entry_log)
        _record_compile_phase(
            entry.compile_id, "xla_compile", entry.stats.first_run_s,
            log=_entry_log, cache=cache_verdict,
        )
        if _hlo_audit_enabled():
            _maybe_hlo_audit(entry, log=_entry_log)
        if obsm.enabled():
            # The entry's first run is where jax.jit actually compiles: this
            # is the end-to-end XLA compile cost per compile class — the
            # total that can silently double while per-pass ms stays flat.
            obsm.XLA_COMPILE_S.observe(
                entry.stats.first_run_s,
                cls="bucketed" if entry.sym_spec is not None else "exact",
            )
        if entry.epilogue_fn is not None:
            result = entry.epilogue_fn(args, kwargs, flat_inps, result)
        return result, dict(record("miss", run_start), compile_s=(run_stop - compile_start) / 1e9)

    fn_._lc_cd = cd
    fn_._lc_cs = cs
    _live_functions.add(fn_)  # ops-plane /debug/state enumeration
    return fn_


# =============================================================================
# Autodiff entry points (reference: thunder/__init__.py `grad:888`)
# =============================================================================


def grad(fn: Optional[Callable] = None, **jit_kwargs) -> Callable:
    """Compile ``fn`` (a scalar-loss function) into a function returning
    gradients w.r.t. its float tensor inputs, staged fw+bw under one XLA jit.

    Grads are returned as a tuple ordered like the function's float tensor
    leaves (pytree inputs are flattened in argument order).

    ``grad(vmap(f))`` composes: the pullback of the batched program is taken
    with ones cotangents on every output — the reference's value_and_grad
    semantics for non-scalar outputs (transforms.py:3704 seeds
    ``ones_like``)."""
    if fn is None:
        return functools.partial(grad, **jit_kwargs)
    if getattr(fn, "_lc_vmap_spec", None) is not None:
        return _grad_of_vmapped(fn, return_value=False, jit_kwargs=jit_kwargs)
    from thunder_tpu.transforms.autodiff import grad_transform

    return jit(fn, _trace_transforms=(lambda trc: grad_transform(trc, return_value=False),), **jit_kwargs)


def value_and_grad(fn: Optional[Callable] = None, **jit_kwargs) -> Callable:
    """Like :func:`grad` but returns ``(value, grads)``."""
    if fn is None:
        return functools.partial(value_and_grad, **jit_kwargs)
    if getattr(fn, "_lc_vmap_spec", None) is not None:
        return _grad_of_vmapped(fn, return_value=True, jit_kwargs=jit_kwargs)
    from thunder_tpu.transforms.autodiff import grad_transform

    return jit(fn, _trace_transforms=(lambda trc: grad_transform(trc, return_value=True),), **jit_kwargs)


def _grad_of_vmapped(vfn: Callable, *, return_value: bool,
                     jit_kwargs: Optional[dict] = None) -> Callable:
    """grad/value_and_grad of a :func:`vmap`-ed function.

    The batched staged program's pullback is evaluated with ones cotangents
    (reference value_and_grad semantics for non-scalar outputs) w.r.t. the
    FLOAT tensor leaves, all under one jax.jit. Staging is cached on input
    metadata like vmap itself. Of jit()'s options only ``executors`` applies
    on this path (there is no prologue/cache machinery to configure) — any
    other option is rejected loudly rather than silently dropped."""
    import jax
    import jax.numpy as jnp

    jit_kwargs = dict(jit_kwargs or {})
    user_executors = jit_kwargs.pop("executors", None)
    if jit_kwargs:
        raise ValueError(
            f"grad(vmap(f)) supports only the 'executors' option; got "
            f"{sorted(jit_kwargs)}"
        )
    executor_stacks = (
        (user_executors, ["jax"]) if user_executors is not None else (None, ["jax"])
    )

    spec = vfn._lc_vmap_spec
    inner_fn, inner_tts = _unwrap_compiled(spec["fn"])
    in_axes, out_axes = spec["in_axes"], spec["out_axes"]
    cache: dict = {}
    cs = CompileStats()

    def wrapper(*args, **kwargs):
        cs.calls += 1
        axes, flat_axes, flat_args = _vmap_flatten(args, kwargs, in_axes)
        diff_idx = tuple(
            i for i, x in enumerate(flat_args) if jnp.issubdtype(x.dtype, jnp.floating)
        )

        key = _meta_key(
            tree_flatten((args, kwargs))[0], extra=(tuple(flat_axes), out_axes, return_value)
        )
        staged = cache.get(key)
        if staged is not None:
            cs.cache_hits += 1
            result = staged(*flat_args)
            return result if return_value else result[1]
        cs.cache_misses += 1

        example = _vmap_example(args, axes)
        for ex_list in executor_stacks:
            flat_fn = _staged_flat_fn(
                inner_fn, example, kwargs, executors=ex_list, trace_transforms=inner_tts
            )
            batched = jax.vmap(flat_fn, in_axes=flat_axes, out_axes=out_axes)

            def vg(*flat, _batched=batched):
                def diff_only(*diff):
                    full = list(flat)
                    for i, d in zip(diff_idx, diff):
                        full[i] = d
                    return _batched(*full)

                out, pullback = jax.vjp(diff_only, *[flat[i] for i in diff_idx])
                cts = tree_map(jnp.ones_like, out)
                grads = pullback(cts)
                return out, grads

            staged = jax.jit(vg)
            try:
                result = staged(*flat_args)
            except Exception as e:  # noqa: BLE001 — narrowly re-matched below
                if ex_list is not None or not _is_kernel_transform_error(e):
                    raise
                continue
            cache[key] = staged
            return result if return_value else result[1]

    wrapper._lc_cs = cs
    return wrapper


# =============================================================================
# Function transforms: vmap / jvp (reference: transforms.py:2051,2324 —
# experimental there; here they compose at the staged-function level, where
# XLA's native batching/forward-mode rules apply to the claimed trace)
# =============================================================================


def _staged_flat_fn(fn: Callable, args: tuple, kwargs: Optional[dict] = None,
                    executors: Optional[Sequence] = None,
                    trace_transforms: Sequence[Callable] = ()) -> Callable:
    """Trace+claim fn for the given example args → flat jax callable whose
    inputs are the TENSOR leaves of (args, kwargs) in pytree order (number/
    string leaves are prologue-guarded constants baked into the trace).
    ``trace_transforms`` (e.g. grad_transform) are what lets vmap compose
    with a grad-compiled function."""
    _, comp = trace_program(fn, args, kwargs or {})
    if getattr(comp, "_input_mutations", None):
        # ADVICE r5 #2: this path re-stages without the jit epilogue, so a
        # function that mutates its inputs would silently lose those writes
        # under vmap/jvp — fail loudly like the grad path does.
        kinds = sorted({m[0] for m in comp._input_mutations})
        raise NotImplementedError(
            f"the traced function mutates its inputs ({', '.join(kinds)}), "
            "which cannot be combined with vmap/jvp re-staging (the mutation "
            "epilogue does not run on this path) — make the function pure or "
            "apply updates outside it"
        )
    compiled = pipeline.compile_trace(pipeline.clean(comp)[-1], resolve_executors(executors),
                                      transforms=trace_transforms)
    return keyed_callable(compiled.claimed)


def _unwrap_compiled(fn: Callable) -> tuple[Callable, tuple]:
    """(inner_fn, trace_transforms) for a thunder-compiled function —
    lets vmap/jvp re-stage the ORIGINAL function with its transforms
    (grad, autocast) instead of tracing through the compiled wrapper."""
    cd = getattr(fn, "_lc_cd", None)
    if cd is not None:
        return cd.fn, tuple(cd.compile_options.get("_trace_transforms", ()))
    return fn, ()


def _is_kernel_transform_error(e: BaseException) -> bool:
    """Narrowly match 'this kernel claim cannot run under the requested jax
    transform' (ADVICE r3: the old blanket TypeError catch masked genuine
    user TypeErrors behind a silent re-stage): a Pallas claim without a
    batching rule raises NotImplementedError mentioning batching/vmap, and a
    custom-VJP claim under jvp raises TypeError mentioning custom_vjp/JVP."""
    msg = str(e).lower()
    if isinstance(e, NotImplementedError):
        return "batching" in msg or "vmap" in msg
    if isinstance(e, TypeError):
        return "custom_vjp" in msg or "jvp" in msg or "custom_jvp" in msg
    return False


def _meta_key(flat_values, extra=()) -> tuple:
    parts = []
    for x in flat_values:
        if bridge.is_concrete_tensor(x):
            shape, dev, dt, rg = bridge.tensor_metadata(x)
            parts.append((tuple(shape), str(dt)))
        elif isinstance(x, (int, float, bool, str, type(None))):
            parts.append(x)
        else:
            parts.append(type(x).__name__)
    return tuple(parts) + tuple(extra)


def _vmap_flatten(args: tuple, kwargs: dict, in_axes):
    """Normalize per-arg axes and flatten to (axes, flat_axes, flat_args):
    tensor leaves only, kwargs leaves unbatched — the one flattening
    protocol shared by vmap and grad-of-vmap."""
    if isinstance(in_axes, (tuple, list)):
        check(
            len(in_axes) == len(args),
            lambda: f"vmap in_axes has {len(in_axes)} entries but the call has "
                    f"{len(args)} positional arguments",
            ValueError,
        )
        axes = tuple(in_axes)
    else:
        axes = (in_axes,) * len(args)

    flat_axes: list = []
    flat_args: list = []
    for a, ax in zip(args, axes):
        for x in tree_flatten(a)[0]:
            if bridge.is_concrete_tensor(x):
                flat_axes.append(ax)
                flat_args.append(bridge.to_jax(x))
    for x in tree_flatten(kwargs)[0]:
        if bridge.is_concrete_tensor(x):
            flat_axes.append(None)
            flat_args.append(bridge.to_jax(x))
    return axes, flat_axes, flat_args


def _vmap_example(args: tuple, axes: tuple) -> tuple:
    """Slice axis-0 (per the in_axes) off every batched tensor leaf — the
    one-slice example the staged trace is acquired on."""

    def slice_ax(x, ax):
        if ax is None or not hasattr(x, "shape"):
            return x
        import numpy as np

        return np.asarray(x).take(0, axis=ax)

    return tuple(
        tree_map(lambda x, _ax=ax: slice_ax(x, _ax), a) for a, ax in zip(args, axes)
    )


def vmap(fn: Callable, in_axes=0, out_axes=0) -> Callable:
    """Vectorizing map over the traced program (experimental; reference
    transforms.py `vmap:2051` is experimental too).

    Traces ``fn`` on one slice with the FULL executor list (kernel claims
    included), then batches the staged callable under ``jax.vmap``; if a
    claimed kernel has no batching rule, the call transparently re-stages
    with the jax executor only. kwargs are passed through unbatched.

    Staging is cached on input metadata (shapes/dtypes/axes): repeat calls
    do zero tracing (observable via ``compile_stats(vmapped)``).

    Composes with :func:`grad`/:func:`value_and_grad`: ``vmap(grad(f))``
    re-stages the ORIGINAL f with its grad transform and batches the staged
    gradient program (per-sample gradients, reference: transforms.py:2051)."""
    import jax

    inner_fn, inner_tts = _unwrap_compiled(fn)
    cache: dict = {}
    cs = CompileStats()

    def vmapped(*args, **kwargs):
        cs.calls += 1
        # The staged computation's inputs are the TENSOR leaves only (number/
        # string leaves are prologue-guarded constants baked into the trace).
        axes, flat_axes, flat_args = _vmap_flatten(args, kwargs, in_axes)

        # The key must cover EVERY leaf (scalars included): non-tensor leaves
        # are baked into the staged trace as constants, so a changed scalar
        # must be a cache miss, not a silent reuse.
        key = _meta_key(
            tree_flatten((args, kwargs))[0], extra=(tuple(flat_axes), out_axes)
        )
        batched = cache.get(key)
        if batched is not None:
            cs.cache_hits += 1
            return batched(*flat_args)
        cs.cache_misses += 1

        # Trace on one slice; batch the staged function. Per-arg in_axes
        # apply to every tensor leaf of that arg (pytree args included).
        example = _vmap_example(args, axes)
        cs.last_trace_tracing_start = timer_ns()
        for ex_list in (None, ["jax"]):
            flat_fn = _staged_flat_fn(
                inner_fn, example, kwargs, executors=ex_list, trace_transforms=inner_tts
            )
            batched = jax.jit(jax.vmap(flat_fn, in_axes=flat_axes, out_axes=out_axes))
            try:
                result = batched(*flat_args)
            except Exception as e:  # noqa: BLE001 — narrowly re-matched below
                if ex_list is not None or not _is_kernel_transform_error(e):
                    raise
                # A claimed kernel without a batching rule: fall back to the
                # pure-jax claiming and let XLA batch the decomposition.
                continue
            cs.last_trace_tracing_stop = timer_ns()
            cache[key] = batched
            return result

    vmapped._lc_cs = cs
    vmapped._lc_vmap_spec = {"fn": fn, "in_axes": in_axes, "out_axes": out_axes}
    return vmapped


class _JvpCache:
    """Staged-jvp cache keyed on a WEAKREF to the function, not ``id(fn)``.

    ``id(fn)`` aliases after GC — a new closure at a reused address would
    silently receive a dead function's staged callable (ADVICE r4). A
    weakref key can't alias (entries are purged the moment the function
    dies) and holds no reference to the closure or anything it captures
    (the cached staged callable is built from the trace, not from ``fn``).
    Non-weakrefable callables fall back to a strong key (bounded by the
    LRU); unhashable callables simply skip caching. Eviction is LRU, not
    the previous clear-all."""

    MAX_ENTRIES = 256

    def __init__(self):
        from collections import OrderedDict

        self._entries = OrderedDict()

    def _purge(self, dead_ref) -> None:
        for k in [k for k in self._entries if k[0] is dead_ref]:
            del self._entries[k]

    def get(self, fn, key):
        import weakref

        try:
            ref = weakref.ref(fn)
        except TypeError:
            ref = fn
        try:
            value = self._entries.get((ref, key))
        except TypeError:  # unhashable callable: never cached
            return None
        if value is not None:
            self._entries.move_to_end((ref, key))
        return value

    def put(self, fn, key, value) -> None:
        import weakref

        try:
            ref = weakref.ref(fn, self._purge)
        except TypeError:
            ref = fn
        try:
            self._entries[(ref, key)] = value
            self._entries.move_to_end((ref, key))
        except TypeError:  # unhashable callable: skip caching
            return
        while len(self._entries) > self.MAX_ENTRIES:
            self._entries.popitem(last=False)

    def __len__(self) -> int:
        return len(self._entries)

    def clear(self) -> None:
        self._entries.clear()


_jvp_cache = _JvpCache()


def jvp(fn: Callable, primals: tuple, tangents: tuple):
    """Forward-mode derivative of the traced program (experimental;
    reference `jvp:2324`). Kernel claims are attempted first; custom-VJP
    kernels (no JVP rule) transparently re-stage with the jax executor.
    Staging is cached per (fn, input metadata) — repeat calls don't retrace."""
    import jax

    flat_p = [bridge.to_jax(x) for x in tree_flatten((tuple(primals), {}))[0]
              if bridge.is_concrete_tensor(x)]
    flat_t = [bridge.to_jax(x) for x in tree_flatten((tuple(tangents), {}))[0]
              if bridge.is_concrete_tensor(x)]
    # Key over every primal leaf — non-tensor primals are baked constants.
    key = _meta_key(tree_flatten((tuple(primals), {}))[0])
    cached = _jvp_cache.get(fn, key)
    if cached is not None:
        return jax.jvp(cached, tuple(flat_p), tuple(flat_t))
    for ex_list in (None, ["jax"]):
        flat_fn = _staged_flat_fn(fn, tuple(primals), executors=ex_list)
        try:
            result = jax.jvp(flat_fn, tuple(flat_p), tuple(flat_t))
        except Exception as e:  # noqa: BLE001 — narrowly re-matched below
            if ex_list is not None or not _is_kernel_transform_error(e):
                raise
            continue
        _jvp_cache.put(fn, key, flat_fn)
        return result


# =============================================================================
# Introspection (reference: thunder/__init__.py:697-793)
# =============================================================================


def _get_cs(fn: Callable) -> CompileStats:
    cs = getattr(fn, "_lc_cs", None)
    check(cs is not None, "Not a thunder_tpu-compiled function", ValueError)
    return cs


def _get_cd(fn: Callable) -> CompileData:
    cd = getattr(fn, "_lc_cd", None)
    check(cd is not None, "Not a thunder_tpu-compiled function", ValueError)
    return cd


def compile_data(fn: Callable) -> CompileData:
    return _get_cd(fn)


def compile_stats(fn: Callable) -> CompileStats:
    return _get_cs(fn)


def last_traces(fn: Callable) -> list:
    return _get_cs(fn).last_traces


def last_prologue_traces(fn: Callable) -> list:
    return _get_cs(fn).last_prologue_traces


def last_backward_traces(fn: Callable) -> list:
    return _get_cs(fn).last_backward_traces


def cache_hits(fn: Callable) -> int:
    return _get_cs(fn).cache_hits


def cache_misses(fn: Callable) -> int:
    return _get_cs(fn).cache_misses


def last_compile_options(fn: Callable) -> dict:
    return _get_cd(fn).last_compile_options()
