"""HLO-level static auditor: the compiled-executable twin of the trace verifier.

The PR 10/12 static-analysis stack (liveness planner, ScheduleCertificate,
comm scheduler) reads *traces* — it only sees collectives the program spells
out as ``dist_prims``. The production pjit path (``parallel/train.py
build_train_step``) spells out none: its collectives are inserted by the XLA
SPMD partitioner during lowering and are invisible to every trace-level rule
(ROADMAP item 3). This module closes that blind spot by auditing the artifact
the partitioner actually produced: the compiled-HLO text, reached through the
same access path the measured half already trusts
(``attribution.scope_map_of`` → ``lowered.compile().as_text()``).

Pipeline:

1. **Parse** the HLO text into computations of :class:`HloOp`s — one shared
   line lexer (:func:`iter_op_metadata` is the second consumer, backing
   ``observability/attribution.hlo_scope_map`` so the two HLO readers cannot
   drift).
2. **Classify** every op: collective family (all-gather / all-reduce /
   reduce-scatter / collective-permute / ...), fusion, layout copy, host
   transfer; collectives are split into *partitioner-inserted* vs *explicit*
   by whether their ``op_name`` metadata scope resolves to a trace-level
   collective symbol. A CPU/GPU-partitioner idiom is recovered structurally:
   an all-reduce whose every consumer slices a strict shard of its output is
   a reduce-scatter the backend chose to spell as all-reduce+slice, and is
   classified (and priced, at the (g−1)/g ring factor) as ``reduce-scatter``
   with ``derived=True``.
3. **Price** each op against the PR 5 cost model
   (:func:`analysis.cost.hlo_op_cost` — the HLO-op → FLOPs/HBM/ICI rules,
   shapes and dtypes parsed from the HLO types).
4. **Schedule-analyze**: the happens-before / exposed-wire analysis of
   ``sched.exposed-collective`` re-run at HLO level — per collective site,
   the roofline compute between the site and its first consumer is the
   overlap window; windows share a per-op budget so two sites never claim
   the same fusion. The resulting :class:`HloScheduleReport` carries
   ``exposed_pct``.

Advisory by construction: the ``hlo.*`` verifier rules report INFO/WARNING
only, and the ``api.py`` compile phase wraps the whole audit in a
``sharp_edge`` guard — a corrupted HLO text never fails a compile.

User entry point: ``thunder_tpu.examine.hlo_report(fn, *args)``.
Docs: docs/performance.md (§HLO auditor), docs/trace_invariants.md (rules).
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Any, Iterator, Optional

from thunder_tpu.analysis.diagnostics import Diagnostic, Severity
from thunder_tpu.analysis.registry import register_rule

__all__ = [
    "HloOp",
    "HloComputation",
    "HloModule",
    "HloCollectiveSite",
    "HloScheduleReport",
    "parse_hlo_module",
    "iter_op_metadata",
    "audit_hlo",
    "audit_jitted",
]


# =============================================================================
# Shared line lexer (one tokenizer, two consumers)
# =============================================================================

# One instruction per line: `%name = <type> <opcode>(<operands>), attrs...`.
# The metadata sub-pattern is the exact historical `attribution._HLO_META_RE`
# so the scope-map consumer stays byte-identical across the refactor.
_NAME_META_RE = re.compile(r"%([\w.\-]+)\s*=.*?op_name=\"([^\"]+)\"")
_INSTR_HEAD_RE = re.compile(r"^\s*(ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
_COMP_HEAD_RE = re.compile(r"^(ENTRY\s+)?%([\w.\-]+)\s*\(")
_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_COMMENT_RE = re.compile(r"/\*.*?\*/")
_OP_NAME_RE = re.compile(r"op_name=\"([^\"]+)\"")
_CHANNEL_RE = re.compile(r"channel_id=(\d+)")
_REPLICA_GROUPS_RE = re.compile(r"replica_groups=\{\{([0-9,]*)\}")
_REPLICA_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_SOURCE_TARGET_RE = re.compile(r"source_target_pairs=\{\{")
_CALLS_RE = re.compile(r"calls=%([\w.\-]+)")
_TO_APPLY_RE = re.compile(r"to_apply=%([\w.\-]+)")
_BODY_RE = re.compile(r"body=%([\w.\-]+)")
_COND_RE = re.compile(r"condition=%([\w.\-]+)")
_LHS_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_CUSTOM_TARGET_RE = re.compile(r"custom_call_target=\"([^\"]+)\"")


def iter_op_metadata(hlo_text: str) -> Iterator[tuple[str, str]]:
    """Yield ``(hlo op name, metadata op_name)`` per instruction line carrying
    ``op_name`` metadata — the lexer slice behind
    ``observability/attribution.hlo_scope_map`` (its historical per-line
    regex semantics: one entry per line, later duplicates overwrite)."""
    for m in _NAME_META_RE.finditer(hlo_text):
        yield m.group(1), m.group(2)


# HLO primitive-type widths in bytes (sub-byte types rounded up: the HBM
# picture of a packed s4 tensor is still byte-granular per XLA's layouts).
HLO_DTYPE_BYTES: dict[str, int] = {
    "pred": 1, "s2": 1, "u2": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "f8e5m2": 1, "f8e4m3": 1, "f8e4m3fn": 1, "f8e4m3b11fnuz": 1,
    "f8e5m2fnuz": 1, "f8e4m3fnuz": 1, "f8e3m4": 1, "f8e8m0fnu": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "c64": 8,
    "s64": 8, "u64": 8, "f64": 8, "c128": 16,
    "token": 0, "opaque": 0,
}


def _dtype_bytes(dtype: str) -> int:
    return HLO_DTYPE_BYTES.get(dtype, 4)


def _dtype_class(dtype: str) -> str:
    """Peak-FLOPs class of an HLO primitive type (DeviceSpec.peak_flops key)."""
    n = _dtype_bytes(dtype)
    if dtype.startswith(("s", "u", "pred")):
        return "int8" if n <= 1 else "f32"
    return "bf16" if n <= 2 else "f32"


def _numel(dims: tuple) -> float:
    n = 1.0
    for d in dims:
        n *= d
    return n


# Collective opcodes; `-start`/`-done` suffixes map onto the same family.
_COLLECTIVE_FAMILIES = frozenset({
    "all-gather", "all-reduce", "reduce-scatter", "collective-permute",
    "all-to-all", "collective-broadcast", "ragged-all-to-all",
})

_HOST_TRANSFER_OPCODES = frozenset({
    "send", "recv", "send-done", "recv-done", "infeed", "outfeed",
})


@dataclass
class HloOp:
    """One parsed HLO instruction with the derived scalars the cost model
    prices (:func:`analysis.cost.hlo_op_cost` consumes exactly these
    fields — keep them in sync with its documented protocol)."""

    name: str
    opcode: str
    result_type: str
    shapes: list  # [(dtype, (dims...)), ...] — tuple results carry several
    operands: list  # operand op names (same computation)
    index: int
    computation: str = ""
    is_root: bool = False
    op_name: str = ""  # metadata op_name path ("" when absent)
    attrs_text: str = ""
    # -- derived, filled by the parser/auditor --
    result_numel: float = 0.0
    result_bytes: float = 0.0
    operand_numel: float = 0.0
    operand_bytes: float = 0.0
    group_size: int = 1
    k_dim: float = 0.0  # dot/conv contraction size
    family: Optional[str] = None  # collective family after classification
    derived: bool = False  # True: all-reduce+slice recovered as reduce-scatter
    calls: Optional[str] = None  # fusion/called computation name

    @property
    def base_family(self) -> Optional[str]:
        """Collective family straight from the opcode (before the derived
        reduce-scatter reclassification), or None."""
        op = self.opcode
        for suffix in ("-start", "-done"):
            if op.endswith(suffix):
                op = op[: -len(suffix)]
        return op if op in _COLLECTIVE_FAMILIES else None

    @property
    def is_collective_site(self) -> bool:
        """True for the issuing op of a collective (`-done` halves excluded)."""
        return self.base_family is not None and not self.opcode.endswith("-done")


@dataclass
class HloComputation:
    name: str
    is_entry: bool = False
    ops: list = field(default_factory=list)
    defs: dict = field(default_factory=dict)  # op name -> index

    def consumers_of(self, name: str) -> list:
        return [op for op in self.ops if name in op.operands]


@dataclass
class HloModule:
    name: str
    computations: list = field(default_factory=list)
    by_name: dict = field(default_factory=dict)

    @property
    def entry(self) -> Optional[HloComputation]:
        for c in self.computations:
            if c.is_entry:
                return c
        return self.computations[-1] if self.computations else None

    @property
    def n_ops(self) -> int:
        return sum(len(c.ops) for c in self.computations)


def _split_result_type(rest: str) -> tuple[str, str]:
    """Split `<type> <opcode>(...)` into (type string, remainder). Tuple
    result types are parenthesized and contain spaces; scalar/array types
    contain none."""
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    return rest[: i + 1], rest[i + 1:].lstrip()
        return rest, ""
    type_str, _, remainder = rest.partition(" ")
    return type_str, remainder.lstrip()


def _split_call(remainder: str) -> tuple[str, str, str]:
    """Split `opcode(operands), attrs` into (opcode, operands, attrs)."""
    lp = remainder.find("(")
    if lp < 0:
        return remainder.strip(), "", ""
    opcode = remainder[:lp].strip()
    depth = 0
    for i in range(lp, len(remainder)):
        ch = remainder[i]
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                return opcode, remainder[lp + 1: i], remainder[i + 1:]
    return opcode, remainder[lp + 1:], ""


def _parse_shapes(type_str: str) -> list:
    return [
        (m.group(1), tuple(int(d) for d in m.group(2).split(",") if d))
        for m in _SHAPE_RE.finditer(type_str)
    ]


def _parse_instruction(line: str, index: int) -> Optional[HloOp]:
    m = _INSTR_HEAD_RE.match(line)
    if m is None:
        return None
    rest = _COMMENT_RE.sub("", m.group(3)).strip()
    type_str, remainder = _split_result_type(rest)
    opcode, operand_str, attrs = _split_call(remainder)
    if not opcode or not opcode[0].isalpha():
        return None
    shapes = _parse_shapes(type_str)
    op = HloOp(
        name=m.group(2),
        opcode=opcode,
        result_type=type_str,
        shapes=shapes,
        operands=_OPERAND_RE.findall(operand_str),
        index=index,
        is_root=bool(m.group(1)),
        attrs_text=attrs,
    )
    nm = _OP_NAME_RE.search(attrs)
    if nm:
        op.op_name = nm.group(1)
    cm = _CALLS_RE.search(attrs)
    if cm:
        op.calls = cm.group(1)
    op.result_numel = sum(_numel(dims) for _, dims in shapes) if shapes else 0.0
    op.result_bytes = sum(_numel(dims) * _dtype_bytes(dt) for dt, dims in shapes)
    op.group_size = _parse_group_size(attrs)
    km = _LHS_CONTRACT_RE.search(attrs)
    if km:
        op._lhs_contract = tuple(int(d) for d in km.group(1).split(",") if d)
    return op


def _parse_group_size(attrs: str) -> int:
    m = _REPLICA_GROUPS_RE.search(attrs)
    if m:
        ids = [t for t in m.group(1).split(",") if t]
        return max(1, len(ids))
    m = _REPLICA_IOTA_RE.search(attrs)
    if m:  # iota v2 format: [num_groups, group_size]<=[...]
        return max(1, int(m.group(2)))
    if _SOURCE_TARGET_RE.search(attrs):
        return 2  # permute: pairwise — factor is 1.0 regardless
    return 1


def parse_hlo_module(hlo_text: str) -> HloModule:
    """Parse compiled-HLO text into an :class:`HloModule` op graph.

    Raises ``ValueError`` when the text contains no parseable computation —
    the signal the advisory wrapper turns into a ``sharp_edge``."""
    if not isinstance(hlo_text, str) or not hlo_text.strip():
        raise ValueError("empty HLO text")
    module_name = ""
    mm = re.match(r"HloModule\s+([\w.\-]+)", hlo_text)
    if mm:
        module_name = mm.group(1)
    module = HloModule(name=module_name)
    current: Optional[HloComputation] = None
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if not stripped or stripped.startswith("//"):
            continue
        if stripped == "}":
            current = None
            continue
        if not line[:1].isspace():
            ch = _COMP_HEAD_RE.match(line)
            if ch and stripped.endswith("{"):
                current = HloComputation(name=ch.group(2), is_entry=bool(ch.group(1)))
                module.computations.append(current)
                module.by_name[current.name] = current
            continue
        if current is None:
            continue
        op = _parse_instruction(line, len(current.ops))
        if op is None:
            continue
        op.computation = current.name
        current.ops.append(op)
        current.defs[op.name] = op.index
    module.computations = [c for c in module.computations if c.ops]
    module.by_name = {c.name: c for c in module.computations}
    if not module.computations:
        raise ValueError("no parseable HLO computations found")
    # Resolve per-op operand totals (operands are in-computation: parameters
    # are instruction lines too) and the dot contraction size.
    for comp in module.computations:
        index = {op.name: op for op in comp.ops}
        for op in comp.ops:
            for o in op.operands:
                src = index.get(o)
                if src is not None:
                    op.operand_numel += src.result_numel
                    op.operand_bytes += src.result_bytes
            if op.opcode in ("dot", "convolution") and op.operands:
                op.k_dim = _contract_k(op, index)
    return module


def _contract_k(op: HloOp, index: dict) -> float:
    lhs = index.get(op.operands[0])
    if lhs is None or not lhs.shapes:
        return 0.0
    dims = lhs.shapes[0][1]
    if op.opcode == "convolution":
        # cin·∏kernel of the weight operand — out-feature dim divided out.
        w = index.get(op.operands[1]) if len(op.operands) > 1 else None
        if w is not None and w.shapes and w.shapes[0][1]:
            wd = w.shapes[0][1]
            return _numel(wd) / max(1, wd[0])
        return 0.0
    contract = getattr(op, "_lhs_contract", None)
    if contract:
        k = 1.0
        for d in contract:
            if 0 <= d < len(dims):
                k *= dims[d]
        return k
    return float(dims[-1]) if dims else 0.0


# =============================================================================
# Classification
# =============================================================================

_SLICE_OPCODES = frozenset({"slice", "dynamic-slice"})


def _is_shard_slice(consumer: HloOp, producer: HloOp, module: HloModule) -> bool:
    """Whether ``consumer`` takes a strict shard of ``producer``'s output —
    a direct slice, or a kLoop fusion whose body slices (the partitioner's
    spelling after fusion)."""
    if consumer.result_numel <= 0 or consumer.result_numel >= producer.result_numel:
        return False
    if consumer.opcode in _SLICE_OPCODES:
        return True
    if consumer.opcode == "fusion" and consumer.calls:
        body = module.by_name.get(consumer.calls)
        if body is not None:
            return any(o.opcode in _SLICE_OPCODES for o in body.ops)
    return False


def _classify_collectives(module: HloModule) -> None:
    """Stamp ``op.family`` on every collective site; recover the
    all-reduce+shard-slice spelling of reduce-scatter (the partitioner emits
    it on backends without a native reduce-scatter pass — every consumer
    slices a strict shard, so the program provably only needs the scattered
    result and the ring only needs to move (g−1)/g of it)."""
    for comp in module.computations:
        consumers: dict[str, list] = {}
        for op in comp.ops:
            for o in op.operands:
                consumers.setdefault(o, []).append(op)
        for op in comp.ops:
            fam = op.base_family
            if fam is None:
                continue
            op.family = fam
            if fam != "all-reduce" or op.opcode.endswith("-done"):
                continue
            cons = [c for c in consumers.get(op.name, []) if c.base_family is None]
            if cons and all(_is_shard_slice(c, op, module) for c in cons):
                op.family = "reduce-scatter"
                op.derived = True


def _scope_sym(op_name: str) -> Optional[str]:
    from thunder_tpu.observability.attribution import parse_scope

    ref = parse_scope(op_name)
    return ref.sym if ref is not None else None


def _is_inserted(op: HloOp) -> bool:
    """Partitioner-inserted vs explicit: an explicit ``dist_prims``
    collective lowers under its own trace line's scope, so its metadata
    scope symbol maps to a collective family; anything else (a compute-op
    scope, or no scope at all) was inserted during partitioning."""
    from thunder_tpu.observability.attribution import COLLECTIVE_SYM_CLASS

    sym = _scope_sym(op.op_name)
    return not (sym is not None and sym in COLLECTIVE_SYM_CLASS)


# =============================================================================
# Schedule analysis + report
# =============================================================================


@dataclass
class HloCollectiveSite:
    """One collective site in the compiled executable: wire bytes/time from
    the cost model, window/hidden from the HLO-level happens-before scan —
    the pjit-path twin of :class:`analysis.schedule.SiteOverlap`."""

    name: str
    opcode: str
    family: str
    computation: str
    index: int
    group_size: int
    wire_bytes: float
    wire_us: float
    window_us: float
    hidden_us: float
    first_consumer: Optional[int] = None
    inserted: bool = True
    derived: bool = False
    scope: str = ""

    @property
    def exposed_us(self) -> float:
        return max(0.0, self.wire_us - self.hidden_us)

    def label(self) -> str:
        return f"{self.computation}/%{self.name}"

    def to_json(self) -> dict:
        return {
            "name": self.name, "opcode": self.opcode, "family": self.family,
            "computation": self.computation, "index": self.index,
            "group_size": self.group_size,
            "wire_bytes": self.wire_bytes,
            "wire_us": round(self.wire_us, 3),
            "window_us": round(self.window_us, 3),
            "hidden_us": round(self.hidden_us, 3),
            "exposed_us": round(self.exposed_us, 3),
            "first_consumer": self.first_consumer,
            "inserted": self.inserted, "derived": self.derived,
            "scope": self.scope,
        }


@dataclass
class HloScheduleReport:
    """Everything the auditor recovered from one compiled executable."""

    module: str
    device: str
    n_ops: int = 0
    n_computations: int = 0
    sites: list = field(default_factory=list)
    by_family: dict = field(default_factory=dict)
    fusions: int = 0
    layout_copies: int = 0
    layout_copy_bytes: float = 0.0
    host_transfers: int = 0
    host_transfer_ops: list = field(default_factory=list)
    flops: float = 0.0
    hbm_bytes: float = 0.0
    comm_bytes: float = 0.0
    compute_us: float = 0.0
    pad_fractions: dict = field(default_factory=dict)
    audit_s: float = 0.0

    @property
    def wire_us(self) -> float:
        return sum(s.wire_us for s in self.sites)

    @property
    def hidden_us(self) -> float:
        return sum(s.hidden_us for s in self.sites)

    @property
    def exposed_us(self) -> float:
        return sum(s.exposed_us for s in self.sites)

    @property
    def exposed_pct(self) -> float:
        """Exposed fraction of total predicted wire time (percent) — the
        static base of ``spmd_collective_exposed_pct``."""
        return self.exposed_us / self.wire_us * 100.0 if self.wire_us else 0.0

    @property
    def inserted_collectives(self) -> int:
        return sum(1 for s in self.sites if s.inserted)

    @property
    def explicit_collectives(self) -> int:
        return sum(1 for s in self.sites if not s.inserted)

    def to_json(self) -> dict:
        return {
            "v": 1,
            "module": self.module,
            "device": self.device,
            "n_ops": self.n_ops,
            "n_computations": self.n_computations,
            "collectives": {k: dict(v) for k, v in sorted(self.by_family.items())},
            "inserted_collectives": self.inserted_collectives,
            "explicit_collectives": self.explicit_collectives,
            "fusions": self.fusions,
            "layout_copies": {"count": self.layout_copies, "bytes": self.layout_copy_bytes},
            "host_transfers": self.host_transfers,
            "flops": self.flops,
            "hbm_bytes": self.hbm_bytes,
            "comm_bytes": self.comm_bytes,
            "compute_us": round(self.compute_us, 3),
            "wire_us": round(self.wire_us, 3),
            "hidden_us": round(self.hidden_us, 3),
            "exposed_us": round(self.exposed_us, 3),
            "exposed_pct": round(self.exposed_pct, 2),
            "pad_fractions": dict(self.pad_fractions),
            "audit_s": self.audit_s,
            "sites": [s.to_json() for s in self.sites],
        }

    def format(self) -> str:
        lines = [
            f"hlo audit [{self.module or 'module'} @ {self.device}]: "
            f"{self.n_ops} ops / {self.n_computations} computations, "
            f"{len(self.sites)} collectives ({self.inserted_collectives} "
            f"partitioner-inserted), {self.fusions} fusions, "
            f"{self.layout_copies} layout copies, {self.host_transfers} host transfers",
            f"  wire {self.wire_us:.1f}us, hidden {self.hidden_us:.1f}us, "
            f"exposed {self.exposed_us:.1f}us ({self.exposed_pct:.1f}%)",
        ]
        for fam, agg in sorted(self.by_family.items()):
            lines.append(
                f"  {fam:<20} n={agg['count']:<3} wire {agg['wire_bytes']/1e6:9.3f} MB"
                f"  {agg['wire_us']:9.1f}us"
            )
        lines.append(
            f"  {'site':<34} {'family':<16} {'wire us':>9} {'window':>9} "
            f"{'hidden':>9} {'exposed':>9}"
        )
        for s in sorted(self.sites, key=lambda s: -s.wire_us)[:20]:
            lines.append(
                f"  {s.label():<34.34} {s.family + ('*' if s.derived else ''):<16} "
                f"{s.wire_us:>9.2f} {s.window_us:>9.2f} {s.hidden_us:>9.2f} "
                f"{s.exposed_us:>9.2f}"
            )
        return "\n".join(lines)

    def __str__(self) -> str:
        return self.format()

    def diagnostics(self) -> list:
        """The ``hlo.*`` rule findings over this report, context-free — what
        ``examine.hlo_report`` prints without needing a trace to verify."""
        diags: list[Diagnostic] = []
        _report_exposed(self, lambda *a, **k: diags.append(_diag(*a, **k)))
        _report_layout_copy(self, lambda *a, **k: diags.append(_diag(*a, **k)))
        _report_padding(self, lambda *a, **k: diags.append(_diag(*a, **k)))
        _report_host_transfer(self, lambda *a, **k: diags.append(_diag(*a, **k)))
        return diags


def _diag(rule: str, severity: Severity, message: str, *, hint: Optional[str] = None,
          bsym_index: Optional[int] = None) -> Diagnostic:
    return Diagnostic(rule=rule, severity=severity, message=message, hint=hint,
                      bsym_index=bsym_index)


def audit_hlo(hlo_text: str, *, device: Any = None,
              pad_fractions: Optional[dict] = None) -> HloScheduleReport:
    """Parse, classify, price, and schedule-analyze one compiled-HLO text.

    Raises on unparseable input (the ``api.py`` phase and ``examine`` wrap
    this in the advisory ``sharp_edge`` guard). ``pad_fractions`` (class
    label → padded-away fraction, from the bucket spec) ride along for the
    ``hlo.padding-waste`` rule."""
    from thunder_tpu.analysis.cost import hlo_op_cost, resolve_device_spec

    dev = resolve_device_spec(device)
    module = parse_hlo_module(hlo_text)
    _classify_collectives(module)

    report = HloScheduleReport(
        module=module.name, device=dev.name,
        n_ops=module.n_ops, n_computations=len(module.computations),
        pad_fractions=dict(pad_fractions or {}),
    )

    # Computations a fusion op calls are priced at their call site (boundary
    # bytes + body FLOPs); everything else (entry, while bodies, reducers)
    # is priced standalone, once.
    fused_comps = {
        op.calls
        for comp in module.computations
        for op in comp.ops
        if op.opcode == "fusion" and op.calls
    }

    def inner_flops(comp_name: Optional[str]) -> float:
        body = module.by_name.get(comp_name or "")
        if body is None:
            return 0.0
        total = 0.0
        for o in body.ops:
            c = hlo_op_cost(o)
            if c is not None:
                total += c.flops
        return total

    for comp in module.computations:
        if comp.name in fused_comps:
            continue
        # def-use within the computation: the happens-before order is the
        # instruction order (compiled modules are scheduled).
        first_consumer: dict[str, int] = {}
        for op in comp.ops:
            for o in op.operands:
                first_consumer.setdefault(o, op.index)

        compute_us: dict[int, float] = {}
        rows: dict[int, tuple] = {}
        for op in comp.ops:
            cost = hlo_op_cost(
                op, inner_flops=inner_flops(op.calls) if op.opcode == "fusion" else 0.0
            )
            if cost is None:
                continue
            dclass = op.shapes[0][0] if op.shapes else "f32"
            t = 0.0
            if cost.flops:
                t = max(t, cost.flops / dev.peak_flops.get(_dtype_class(dclass), dev.peak_flops["f32"]))
            if cost.bytes_moved and dev.hbm_bw:
                t = max(t, cost.bytes_moved / dev.hbm_bw)
            report.flops += cost.flops
            report.hbm_bytes += cost.bytes_moved
            report.comm_bytes += cost.comm_bytes
            rows[op.index] = (cost, t)
            if cost.kind == "fusion":
                report.fusions += 1
            if op.opcode in ("copy", "copy-start"):
                report.layout_copies += 1
                report.layout_copy_bytes += 2.0 * op.result_bytes
            if op.opcode in _HOST_TRANSFER_OPCODES or (
                op.opcode == "custom-call" and _is_host_custom_call(op)
            ) or ":S(" in op.result_type:
                report.host_transfers += 1
                report.host_transfer_ops.append(f"{comp.name}/%{op.name}")
            if not op.is_collective_site:
                compute_us[op.index] = t * 1e6

        # Shared-budget window scan — the exact predict_overlap model, over
        # HLO instruction order: window compute between a site and its first
        # consumer hides wire time; each op's budget is consumed in program
        # order so two sites never claim the same fusion.
        budget = dict(compute_us)
        for op in comp.ops:
            if not op.is_collective_site:
                continue
            cost, _t = rows.get(op.index, (None, 0.0))
            wire_bytes = cost.comm_bytes if cost is not None else 0.0
            fam = op.family or "all-reduce"
            bw = dev.ici_bw_for(fam)
            wire_us = wire_bytes / bw * 1e6 if bw else 0.0
            consumer = first_consumer.get(op.name)
            if consumer is not None:
                done = comp.ops[consumer]
                if done.opcode.endswith("-done"):
                    consumer = first_consumer.get(done.name)
            window = 0.0
            hidden = 0.0
            if consumer is not None:
                for j in range(op.index + 1, consumer):
                    avail = budget.get(j, 0.0)
                    window += compute_us.get(j, 0.0)
                    if avail and hidden < wire_us:
                        take = min(avail, wire_us - hidden)
                        budget[j] = avail - take
                        hidden += take
            site = HloCollectiveSite(
                name=op.name, opcode=op.opcode, family=fam,
                computation=comp.name, index=op.index,
                group_size=op.group_size, wire_bytes=wire_bytes,
                wire_us=wire_us, window_us=window,
                hidden_us=min(hidden, wire_us), first_consumer=consumer,
                inserted=_is_inserted(op), derived=op.derived,
                scope=op.op_name,
            )
            report.sites.append(site)
            agg = report.by_family.setdefault(
                fam, {"count": 0, "wire_bytes": 0.0, "wire_us": 0.0, "inserted": 0}
            )
            agg["count"] += 1
            agg["wire_bytes"] += wire_bytes
            agg["wire_us"] += wire_us
            if site.inserted:
                agg["inserted"] += 1
        report.compute_us += sum(compute_us.values())
    for agg in report.by_family.values():
        agg["wire_us"] = round(agg["wire_us"], 3)
    return report


def _is_host_custom_call(op: HloOp) -> bool:
    m = _CUSTOM_TARGET_RE.search(op.attrs_text)
    return bool(m and "host" in m.group(1).lower())


def audit_jitted(jfn: Any, *args, device: Any = None,
                 pad_fractions: Optional[dict] = None, **kwargs) -> HloScheduleReport:
    """Audit an already-jitted callable (``jax.jit`` object or ``Compiled``),
    lowering on the example args if needed — the same access path as
    ``attribution.scope_map_of``."""
    if hasattr(jfn, "as_text"):
        text = jfn.as_text()
    elif hasattr(jfn, "lower"):
        text = jfn.lower(*args, **kwargs).compile().as_text()
    else:
        raise TypeError(
            f"audit_jitted needs a jax.jit callable or Compiled, got {type(jfn).__name__}"
        )
    return audit_hlo(text, device=device, pad_fractions=pad_fractions)


# =============================================================================
# hlo.* verifier rules (advisory — INFO/WARNING only, never gate a compile)
# =============================================================================

# Sub-µs wire predictions are bookkeeping noise; same floor as sched.*.
_HLO_EXPOSED_MIN_WIRE_US = 1.0
# A layout copy under 1 MiB round-trip is fusion fodder, not a finding.
_HLO_LAYOUT_COPY_MIN_BYTES = float(1 << 20)
# Below a quarter padded-away the bucket policy is working as designed.
_HLO_PAD_WASTE_MIN_FRAC = 0.25


def _audit_report_of(ctx) -> Optional[HloScheduleReport]:
    tags = getattr(ctx.trace, "tags", None)
    rep = tags.get("hlo_audit") if isinstance(tags, dict) else None
    return rep if isinstance(rep, HloScheduleReport) else None


def _report_exposed(rep: HloScheduleReport, emit) -> None:
    for s in rep.sites:
        if s.wire_us < _HLO_EXPOSED_MIN_WIRE_US or s.exposed_us <= 0.0:
            continue
        kind = "partitioner-inserted" if s.inserted else "explicit"
        emit(
            "hlo.exposed-collective",
            Severity.INFO,
            f"{s.label()} [{s.family}{'*' if s.derived else ''}, {kind}]: "
            f"predicted {s.exposed_us:.1f}us of {s.wire_us:.1f}us wire exposed "
            f"({s.hidden_us:.1f}us hidden under the {s.window_us:.1f}us window "
            "to its first consumer)",
            hint="partitioner-inserted sites need XLA-side levers (sharding "
            "hints, xla_tpu_enable_async_collective_* flags, latency-hiding "
            "scheduler budget) — the trace-level comm scheduler cannot move "
            "ops it cannot see (ROADMAP item 3)",
        )


def _report_layout_copy(rep: HloScheduleReport, emit) -> None:
    if rep.layout_copies == 0 or rep.layout_copy_bytes < _HLO_LAYOUT_COPY_MIN_BYTES:
        return
    emit(
        "hlo.layout-copy",
        Severity.INFO,
        f"{rep.layout_copies} layout copies move {rep.layout_copy_bytes/1e6:.2f} MB "
        "through HBM in the compiled executable",
        hint="a copy is XLA materializing a layout change the program forced "
        "(transpose chains, mixed minor-to-major constraints); align the "
        "producing op's layout or fuse the consumer",
    )


def _report_padding(rep: HloScheduleReport, emit) -> None:
    for label, frac in sorted(rep.pad_fractions.items()):
        if frac < _HLO_PAD_WASTE_MIN_FRAC:
            continue
        emit(
            "hlo.padding-waste",
            Severity.WARNING,
            f"bucket dim {label}: {frac * 100.0:.0f}% of the padded extent is "
            "padding — every op touching it pays full-bucket FLOPs/HBM",
            hint="a tighter BucketPolicy (smaller multiple, or pow2 → multiple) "
            "trades recompiles for less padded compute; core/bucketing.py",
        )


def _report_host_transfer(rep: HloScheduleReport, emit) -> None:
    if rep.host_transfers == 0:
        return
    ops = ", ".join(rep.host_transfer_ops[:4])
    emit(
        "hlo.host-transfer-in-step",
        Severity.WARNING,
        f"{rep.host_transfers} host transfer(s) inside the compiled step "
        f"({ops}{'…' if rep.host_transfers > 4 else ''})",
        hint="a host round-trip serializes the device pipeline every step; "
        "move the offending computation on-device or out of the step",
    )


def _make_rule(reporter):
    def rule(ctx) -> None:
        rep = _audit_report_of(ctx)
        if rep is None:
            return
        reporter(rep, lambda rule_id, sev, msg, **kw: ctx.report(rule_id, sev, msg, **kw))
    return rule


register_rule(
    "hlo.exposed-collective",
    "Partitioner-inserted collective wire time is predicted hidden at HLO level",
)(_make_rule(_report_exposed))
register_rule(
    "hlo.layout-copy",
    "Compiled executable materializes significant layout-change copies",
)(_make_rule(_report_layout_copy))
register_rule(
    "hlo.padding-waste",
    "Bucket padding wastes a large fraction of every padded dim's compute",
)(_make_rule(_report_padding))
register_rule(
    "hlo.host-transfer-in-step",
    "Compiled step round-trips through the host",
)(_make_rule(_report_host_transfer))
