"""Static HBM liveness planner over claimed execution traces.

The *memory* third of the static trace planner suite (ISSUE 10; the other
two are ``analysis/schedule.py`` and the donation sanitizer in
``analysis/rules.py``): every value-producing BoundSymbol's tensor outputs
are assigned byte sizes from their proxy metadata alone — dtype-aware,
bucket-padding-aware (a symbolic trace's shapes ARE the padded bucket
ceilings), sharding-divided when the caller supplies PartitionSpec divisors
— and an interval walk over the program computes the per-line live set and
its peak: the predicted per-device HBM high-water of running the trace.

Lifetime model (documented so the golden tests are exact):

- trace inputs are live from entry. Non-donated inputs stay live to the end
  (the caller holds the buffer; XLA cannot reuse it). A **donated** input
  dies at its last use — donation is precisely the license to reuse it.
- every produced tensor goes live at its producing line and dies after its
  last consumer, alias-extended (a view's use keeps its root buffer alive).
  Explicit ``python_del``s (post ``del_last_used``) are ignored for
  freeing: they are per-name, so honoring one would free a root whose
  views still live; the interval analysis frees at the same point when no
  views remain and later when they do, keeping the del'd and un-del'd
  plans of one program equal.
- trace outputs never die (they are returned).
- pure layout/alias ops (reshape/squeeze/broadcast/shallow_copy/
  stop_gradient) charge **zero** bytes — XLA compiles them to views — and
  their uses extend the *root* buffer's lifetime through the alias chain.
- bookkeeping prims (unpacks, guards, del/return/comment) allocate nothing.

The prediction is a *lower bound* on the real high-water (XLA adds
executable temporaries and fragmentation); ``scripts/lint_traces.py
--static`` holds it within 15% of the ``instrument="memory"`` measured
high-water on the GPT-block bench.

Consumers: ``examine.memory_report(fn, *args)`` (user-facing),
the ``mem.predicted-oom`` verifier rule (``THUNDER_TPU_CHECKS=1`` /
``examine.lint``), and the compile de-opt ladder
(``resilience/deopt.py``), which uses :func:`predict_level_peaks` to jump
straight to the first ladder level whose predicted peak fits the device
instead of paying one failed XLA compile per level.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Any, Callable, Optional, Sequence

from thunder_tpu.analysis.cost import DeviceSpec, resolve_device_spec
from thunder_tpu.analysis.diagnostics import Severity
from thunder_tpu.analysis.registry import register_rule
from thunder_tpu.core.prims import PrimIDs
from thunder_tpu.core.proxies import TensorProxy
from thunder_tpu.core.pytree import tree_flatten
from thunder_tpu.core.trace import TraceCtx

# Prims that allocate nothing and touch no tensor lifetimes (guards,
# unpacks, control plumbing). DEL/RETURN are handled explicitly.
_BOOKKEEPING_IDS = {
    PrimIDs.COMMENT, PrimIDs.PRINT,
    PrimIDs.UNPACK_TRIVIAL, PrimIDs.UNPACK_SEQUENCE, PrimIDs.UNPACK_KEY,
    PrimIDs.UNPACK_ATTR, PrimIDs.UNPACK_DIM,
    PrimIDs.CHECK_TENSOR_SHAPE_AND_METADATA, PrimIDs.CHECK_NUMBER_TYPE_AND_VALUE,
    PrimIDs.CHECK_STRING_VALUE, PrimIDs.CHECK_LEN, PrimIDs.CHECK_KEYS,
    PrimIDs.CHECK_NONE, PrimIDs.CHECK_DIM_BUCKET,
}

# Layout/alias ops XLA lowers to views: zero bytes; output aliases arg 0.
_ALIAS_IDS = {
    PrimIDs.RESHAPE, PrimIDs.SQUEEZE, PrimIDs.BROADCAST_IN_DIM,
    PrimIDs.SHALLOW_COPY, PrimIDs.STOP_GRADIENT, PrimIDs.OPTIMIZATION_BARRIER,
}


def build_alias_roots(bsyms) -> dict:
    """``{view name: immediate source name}`` for every alias-op output —
    THE one copy of the view model (first tensor operand is the root),
    shared by the liveness walk, the donation/alias sanitizer rules, and
    the schedule certificate's anti-dependency analysis."""
    alias: dict = {}
    for bsym in bsyms:
        if bsym.sym.id not in _ALIAS_IDS:
            continue
        src = next(
            (p for p in bsym.flat_proxy_args if isinstance(p, TensorProxy)), None
        )
        if src is None:
            continue
        for o in bsym.flat_proxy_outs:
            if isinstance(o, TensorProxy) and o.name != src.name:
                alias[o.name] = src.name
    return alias


def alias_root_fn(bsyms):
    """``root(name) -> name`` resolving through the full view chain."""
    alias = build_alias_roots(bsyms)

    def root(name: str) -> str:
        while name in alias:
            name = alias[name]
        return name

    return root


@dataclass
class LivenessRow:
    """One value-producing trace line's live-set accounting."""

    index: int
    sym: str
    live_bytes: int       # live-set bytes AFTER this line executes
    alloc_bytes: int      # bytes this line's outputs charge
    freed_bytes: int      # bytes whose last use was this line
    line: str = ""


@dataclass
class MemoryPlan:
    """Predicted per-device HBM occupancy of one trace.

    ``peak_bytes`` is the planner's headline number: the maximum live-set
    over the program. ``eager_alloc_bytes`` sums every concrete tensor an
    *unstaged* (instrumented, op-by-op) run would materialize — produced
    tensors only, inputs excluded — the number comparable to
    ``MemoryHighWater``'s cumulative fallback estimate on backends without
    ``memory_stats`` (the CPU plugin; ``lint_traces.py --static`` uses
    whichever comparison the backend supports)."""

    device: DeviceSpec
    peak_bytes: int = 0
    peak_index: Optional[int] = None
    peak_sym: Optional[str] = None
    input_bytes: int = 0
    output_bytes: int = 0
    total_alloc_bytes: int = 0
    eager_alloc_bytes: int = 0
    donated_names: tuple = ()
    rows: list = field(default_factory=list)

    def fits(self, capacity_bytes: Optional[int] = None) -> bool:
        cap = capacity_bytes if capacity_bytes is not None else device_capacity_bytes(self.device)
        return cap is None or self.peak_bytes < cap

    def headroom(self, capacity_bytes: Optional[int] = None) -> Optional[float]:
        """capacity / predicted peak (None when capacity is unknown)."""
        cap = capacity_bytes if capacity_bytes is not None else device_capacity_bytes(self.device)
        if cap is None or not self.peak_bytes:
            return None
        return cap / self.peak_bytes

    def format(self, top_k: int = 8) -> str:
        cap = device_capacity_bytes(self.device)
        lines = [
            f"memory plan [{self.device.name}"
            + (f": {cap / 1e9:.1f} GB HBM]" if cap else "]"),
            f"  predicted peak: {self.peak_bytes / 1e6:.2f} MB"
            + (f" at L{self.peak_index} ({self.peak_sym})" if self.peak_index is not None else "")
            + (f" — {self.peak_bytes / cap * 100:.1f}% of device" if cap else ""),
            f"  inputs {self.input_bytes / 1e6:.2f} MB"
            + (f" ({len(self.donated_names)} donated)" if self.donated_names else "")
            + f", outputs {self.output_bytes / 1e6:.2f} MB, "
            f"total allocated {self.total_alloc_bytes / 1e6:.2f} MB",
        ]
        hottest = sorted(self.rows, key=lambda r: r.live_bytes, reverse=True)[:top_k]
        if hottest:
            lines.append(f"  {'line':>6} {'sym':<28} {'live MB':>10} {'alloc MB':>10}")
            for r in hottest:
                lines.append(
                    f"  L{r.index:>5} {r.sym:<28.28} {r.live_bytes / 1e6:>10.3f} "
                    f"{r.alloc_bytes / 1e6:>10.3f}"
                )
        return "\n".join(lines)

    def __str__(self) -> str:
        return self.format()


# The backend bytes_limit probe never changes within a process; memoized so
# the mem.predicted-oom rule (which runs per pass under THUNDER_TPU_CHECKS=1)
# pays one backend query per process, not one per verify().
_backend_limit_cache: dict = {}


def _backend_bytes_limit() -> Optional[int]:
    if "limit" not in _backend_limit_cache:
        limit = None
        try:
            import jax

            stats = jax.local_devices()[0].memory_stats()
            if stats and stats.get("bytes_limit"):
                limit = int(stats["bytes_limit"])
        except Exception:
            pass
        _backend_limit_cache["limit"] = limit
    return _backend_limit_cache["limit"]


def device_capacity_bytes(device: Any = None) -> Optional[int]:
    """Usable HBM bytes of one device: the ``THUNDER_TPU_HBM_BYTES`` env
    override first (tests, and operators who know their binary's reserved
    fraction; re-read every call so scoped overrides work), then the live
    backend's ``memory_stats()['bytes_limit']`` (memoized per process),
    then the spec's datasheet capacity. None when nothing is known."""
    env = os.environ.get("THUNDER_TPU_HBM_BYTES", "").strip()
    if env:
        try:
            return int(float(env))
        except ValueError:
            pass
    limit = _backend_bytes_limit()
    if limit:
        return limit
    try:
        spec = resolve_device_spec(device)
    except Exception:
        return None
    return spec.hbm_bytes or None


def partition_divisor(spec: Any, axis_sizes: dict) -> float:
    """How many ways a PartitionSpec splits a tensor over a mesh: the
    product of the named axes' sizes (axis tuples multiply; None/absent
    axes divide by 1)."""
    div = 1.0
    for part in tuple(spec or ()):
        for ax in (part if isinstance(part, (tuple, list)) else (part,)):
            if ax is not None:
                div *= float(axis_sizes.get(ax, 1))
    return div


def arg_divisors_from_specs(trace: TraceCtx, specs, mesh=None, axis_sizes=None) -> dict:
    """``{input proxy name: shard divisor}`` from a PartitionSpec pytree
    aligned with the trace's tensor args (``parallel/sharding.py`` plans).

    This divides INPUT buffers only: intermediates of a pjit-staged trace
    have no trace-level sharding (the SPMD partitioner decides), so a plan
    built with these divisors is an UPPER BOUND on the per-device peak —
    params at shard size, activations conservatively at global shape.
    Honest for fit checks (an upper bound that fits, fits); not a measured
    per-device number."""
    if axis_sizes is None:
        if mesh is None:
            return {}
        axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    flat_specs, _ = tree_flatten(
        specs, is_leaf=lambda x: type(x).__name__ == "PartitionSpec"
    )
    args = [a for a in tree_flatten((trace.args, trace.kwargs))[0] if isinstance(a, TensorProxy)]
    out: dict[str, float] = {}
    for a, s in zip(args, flat_specs):
        d = partition_divisor(s, axis_sizes)
        if d > 1.0:
            out[a.name] = d
    return out


def _tensor_bytes(p: TensorProxy, divisors: Optional[dict]) -> int:
    b = p.size_bytes
    if divisors:
        d = divisors.get(p.name)
        if d:
            b = int(b / d)
    return int(b)


def plan_liveness(
    trace: TraceCtx,
    *,
    device: Any = None,
    donated: Sequence[str] = (),
    arg_divisors: Optional[dict] = None,
    include_rows: bool = True,
) -> MemoryPlan:
    """Interval-based liveness walk over ``trace`` → :class:`MemoryPlan`.

    ``donated`` names input proxies whose buffers XLA may reuse (they die at
    last use); defaults to the trace's ``donated_inputs`` tag when the
    compile pipeline stamped one. ``arg_divisors`` divides named input
    buffers for sharded (global-shape) traces — see
    :func:`arg_divisors_from_specs`."""
    dev = resolve_device_spec(device)
    plan = MemoryPlan(device=dev)
    if donated == () and trace.tags.get("donated_inputs"):
        donated = tuple(trace.tags["donated_inputs"])
    plan.donated_names = tuple(donated)
    donated_set = set(plan.donated_names)

    bsyms = list(trace.bound_symbols)

    # -- one pass: sizes, alias roots, last-use indexes ------------------------
    sizes: dict[str, int] = {}
    alias_root = build_alias_roots(bsyms)

    def root_of(name: str) -> str:
        while name in alias_root:
            name = alias_root[name]
        return name

    inputs: list[TensorProxy] = [
        a for a in tree_flatten((trace.args, trace.kwargs))[0] if isinstance(a, TensorProxy)
    ]
    for a in inputs:
        sizes.setdefault(a.name, _tensor_bytes(a, arg_divisors))
    input_names = {a.name for a in inputs}
    plan.input_bytes = sum(sizes[a.name] for a in inputs)

    out_names: set[str] = set()
    for p in tree_flatten(trace.output)[0]:
        if isinstance(p, TensorProxy):
            out_names.add(p.name)

    # last_use[root] = index of the last bsym consuming the root (through
    # aliases). Explicit DELs are ignored for freeing: del_last_used emits a
    # del per NAME right after its last use, which would free a view's root
    # buffer while other views still live — the alias-extended interval
    # analysis frees at the same point when no views remain, and later when
    # they do, so the del'd and un-del'd plans of one program agree.
    last_use: dict[str, int] = {}
    for i, bsym in enumerate(bsyms):
        sid = bsym.sym.id
        if sid is PrimIDs.DEL:
            continue
        for p in bsym.flat_proxy_args:
            if isinstance(p, TensorProxy):
                last_use[root_of(p.name)] = i

    # Invert last_use once: dying_at[i] = root names whose final consumer is
    # line i. The walk is then O(bsyms + values) instead of rescanning the
    # whole live set per line (the planner runs on every compile — its
    # seconds are a gated compile phase).
    dying_at: dict[int, list] = {}
    for name, i in last_use.items():
        dying_at.setdefault(i, []).append(name)

    # -- the walk --------------------------------------------------------------
    live: dict[str, int] = {}
    for a in inputs:
        live[a.name] = sizes[a.name]
    cur = sum(live.values())
    plan.peak_bytes = cur
    plan.total_alloc_bytes = cur

    def free(name: str, idx: int) -> int:
        """Free ``name`` if it may die: never outputs; inputs only when
        donated."""
        r = root_of(name)
        if r in out_names or (r in input_names and r not in donated_set):
            return 0
        return live.pop(r, 0)

    for i, bsym in enumerate(bsyms):
        sid = bsym.sym.id
        if sid in (PrimIDs.RETURN,):
            break
        if sid is PrimIDs.DEL or sid in _BOOKKEEPING_IDS:
            continue
        alloc = 0
        eager = 0
        arg_names = {p.name for p in bsym.flat_proxy_args}
        for o in bsym.flat_proxy_outs:
            if not isinstance(o, TensorProxy) or o.name in arg_names:
                continue
            b = _tensor_bytes(o, arg_divisors)
            sizes.setdefault(o.name, b)
            eager += b
            if sid in _ALIAS_IDS or o.name in alias_root:
                continue  # view: no new buffer
            if o.name not in live:
                live[o.name] = b
                alloc += b
        cur += alloc
        plan.total_alloc_bytes += alloc
        plan.eager_alloc_bytes += eager
        if cur > plan.peak_bytes:
            plan.peak_bytes = cur
            plan.peak_index = i
            plan.peak_sym = bsym.sym.name
        # Free every value whose (alias-extended) last use was this line.
        freed = 0
        dying = dying_at.get(i)
        if dying:
            out_here = {
                o.name for o in bsym.flat_proxy_outs if isinstance(o, TensorProxy)
            }
            for name in dying:
                if name not in out_here:
                    freed += free(name, i)
        cur -= freed
        if include_rows and (alloc or freed or bsym.flat_proxy_outs):
            plan.rows.append(LivenessRow(
                index=i, sym=bsym.sym.name, live_bytes=int(cur),
                alloc_bytes=int(alloc), freed_bytes=int(freed),
            ))

    plan.output_bytes = sum(sizes.get(root_of(n), 0) for n in out_names)
    return plan


# =============================================================================
# De-opt ladder prediction (resilience/deopt.py consults this)
# =============================================================================


def _marked_bytes(sym_spec, true_extents: Optional[dict],
                  arg_proxies: Optional[Sequence]) -> Optional[tuple]:
    """(padded_bytes, exact_bytes) summed over the marked input leaves —
    full numel × dtype bytes with marked dims at the bucket ceiling vs the
    failing call's exact extents (two marked dims of one leaf multiply).
    None when the spec, extents, or shapes are unknown."""
    if sym_spec is None or not true_extents:
        return None
    padded = 0.0
    exact = 0.0
    for li, dims in sym_spec.marks.items():
        if arg_proxies is None or li >= len(arg_proxies):
            return None
        p = arg_proxies[li]
        if not isinstance(p, TensorProxy):
            return None
        padded_numel = float(p.numel)
        exact_numel = padded_numel
        for d, (lo, hi, cid) in dims.items():
            e = true_extents.get(cid)
            if e is None or not hi:
                return None
            exact_numel *= float(e) / float(hi)
        padded += padded_numel * p.dtype.bytes
        exact += exact_numel * p.dtype.bytes
    if not padded:
        return None
    return padded, exact


def exact_shape_scale(sym_spec, true_extents: Optional[dict],
                      arg_proxies: Optional[Sequence] = None) -> Optional[float]:
    """Byte ratio exact/padded over the marked input leaves — how much the
    de-opt ladder's L3 ("exact shapes") shrinks the bucket-padded
    activations. A true byte ratio: each marked leaf contributes its full
    numel × dtype bytes with marked dims at the padded ceiling vs the
    failing call's exact extents (two marked dims of one leaf multiply;
    unmarked dims and dtype weight each leaf correctly — a tiny mask leaf
    cannot dilute a huge activation's shrinkage). ``arg_proxies`` are the
    trace's tensor args, aligned with the spec's leaf indices. None when
    the spec, extents, or shapes are unknown — the caller must treat that
    level as unprovable, never skippable."""
    mb = _marked_bytes(sym_spec, true_extents, arg_proxies)
    if mb is None:
        return None
    return _scale_of(*mb)


def _scale_of(padded_bytes: float, exact_bytes: float) -> float:
    """THE clamped byte-ratio formula — one copy, shared by
    :func:`exact_shape_scale` and the L3 pricing in
    :func:`predict_level_peaks`."""
    return max(min(exact_bytes / padded_bytes, 1.0), 1e-3)


def predict_level_peaks(
    trace: TraceCtx,
    *,
    sym_spec=None,
    donated: Sequence[str] = (),
    true_extents: Optional[dict] = None,
    device: Any = None,
    bucketing_unknown: bool = False,
) -> dict[int, Optional[int]]:
    """Predicted per-device peak bytes at each de-opt ladder level
    (``resilience/deopt.py``): L0 as compiled (donation on), L1 donation
    off, L2 = L1 (the ladder's aggressive-remat knob rewrites the module
    fw/bw split, which does not route through this ladder — on the
    functional pipeline L2 compiles the same program as L1). L3 ("exact
    shapes") shrinks BOTH the marked inputs (exact bytes replace padded)
    and the activation share (scaled by the exact/padded byte ratio), so
    the L3 prediction stays a lower bound — the skip logic's "predicted >=
    capacity proves unfit" premise. A ``None`` peak means "unknown — never
    skip this level". ``bucketing_unknown=True`` forces L3 unknown: the
    caller could not tell whether the trace is bucket-padded (e.g. a
    symbolic-cache function failing before its entry exists), so L3 must
    not be "proven" anything from a possibly-padded plan."""
    base = plan_liveness(trace, device=device, donated=donated, include_rows=False)
    # plan_liveness treats donated=() as "consult the trace tag", so the
    # donation-off plan must suppress the tag explicitly.
    no_don = _plan_without_donation(trace, device) if (
        donated or trace.tags.get("donated_inputs")
    ) else base
    peaks: dict[int, Optional[int]] = {
        0: base.peak_bytes,
        1: no_don.peak_bytes,
        2: no_don.peak_bytes,
        3: no_don.peak_bytes,
    }
    args = [a for a in tree_flatten((trace.args, trace.kwargs))[0]
            if isinstance(a, TensorProxy)]
    mb = _marked_bytes(sym_spec, true_extents, args)
    if bucketing_unknown:
        peaks[3] = None
    elif mb is not None:
        # Exact shapes shrink the marked inputs to their exact bytes AND the
        # activation share by the exact/padded byte ratio; unmarked inputs
        # (params) don't shrink. A ratio of exactly 1.0 (the call sits at
        # its bucket ceilings) is a KNOWN peak equal to L1's — provably
        # unfit when L1 is, so the ladder must not burn a compile "trying"
        # L3 on an unknown.
        padded_m, exact_m = mb
        scale = _scale_of(padded_m, exact_m)
        inputs_l3 = max(no_don.input_bytes - padded_m + exact_m, 0.0)
        act = max(no_don.peak_bytes - no_don.input_bytes, 0)
        peaks[3] = int(inputs_l3 + act * scale)
    elif sym_spec is None:
        peaks[3] = no_don.peak_bytes
    else:
        peaks[3] = None  # padded entry, extents unknown: can't prove either way
    return peaks


def _plan_without_donation(trace: TraceCtx, device) -> MemoryPlan:
    tag = trace.tags.pop("donated_inputs", None)
    try:
        return plan_liveness(trace, device=device, include_rows=False)
    finally:
        if tag is not None:
            trace.tags["donated_inputs"] = tag


# =============================================================================
# examine.memory_report
# =============================================================================


def memory_report(fn: Callable, *args, executors: Any = None, device: Any = None,
                  **kwargs) -> MemoryPlan:
    """Trace ``fn`` on the example inputs through the pass pipeline the
    dispatcher runs (``thunder_tpu/pipeline.py``, then del_last_used) and return the
    :class:`MemoryPlan` of the resulting execution trace — the static
    memory half of the planner suite (``examine.memory_report`` re-exports
    this; docs/performance.md).

    For an already-compiled ``thunder_tpu.jit`` function the underlying
    function is traced (mirroring ``examine.cost_report``); the exact plan
    of a compiled entry — donation and bucket padding included — is on the
    entry itself (``cache_info(jfn)`` → ``predicted_peak_bytes``)."""
    from thunder_tpu import pipeline
    from thunder_tpu.api import trace_program
    from thunder_tpu.core.trace import debug_checks
    from thunder_tpu.executors.passes import del_last_used
    from thunder_tpu.extend import resolve_executors

    cd = getattr(fn, "_lc_cd", None)
    if cd is not None:
        fn = cd.fn
    with debug_checks(False):
        _, comp = trace_program(fn, args, kwargs)
        extrace = pipeline.compile_trace(pipeline.clean(comp)[-1], resolve_executors(executors)).claimed
        extrace = del_last_used(extrace)
    return plan_liveness(extrace, device=device)


# =============================================================================
# Verifier rule: predicted OOM
# =============================================================================

# Traces smaller than this are guard/prologue plumbing — planning them would
# only add noise to every verify() call.
_MIN_RULE_BSYMS = 4


@register_rule(
    "mem.predicted-oom",
    "The trace's predicted peak HBM live-set fits the device's capacity",
)
def predicted_oom(ctx) -> None:
    """WARNING when the static live-set peak exceeds the detected device
    capacity: the compile is *predicted* to OOM before XLA spends ~20s
    discovering it (the de-opt ladder consults the same plan to jump
    levels). A warning, not an error — the plan is a lower bound and XLA
    may still fit via donation/aliasing the model can't see."""
    if len(ctx.bsyms) < _MIN_RULE_BSYMS:
        return
    try:
        # Capacity first: on capacity-unknown hosts (CPU spec, no
        # bytes_limit, no env override) the rule can never fire, so don't
        # pay the O(trace) planning walk per pass under checks.
        cap = device_capacity_bytes()
        if not cap:
            return
        plan = plan_liveness(ctx.trace, include_rows=False)
    except Exception:  # noqa: BLE001 — planning must never break verification
        return
    if cap and plan.peak_bytes > cap:
        ctx.report(
            "mem.predicted-oom",
            Severity.WARNING,
            f"predicted peak live-set {plan.peak_bytes / 1e9:.2f} GB exceeds the "
            f"{plan.device.name} device capacity {cap / 1e9:.2f} GB"
            + (f" (peak at L{plan.peak_index}.{plan.peak_sym})"
               if plan.peak_index is not None else ""),
            bsym_index=plan.peak_index,
            hint="expect RESOURCE_EXHAUSTED; shrink the bucket ceilings, enable "
            "donation, or let the de-opt ladder pick a remat level "
            "(resilience/deopt.py consults this same plan)",
        )
