"""Static per-op cost model and roofline analysis over traces.

The *predicted* half of the performance-attribution observatory (the
*measured* half is ``thunder_tpu/observability/attribution.py``): every
value-producing BoundSymbol is assigned FLOPs, HBM bytes, and interconnect
bytes from its tensor metadata alone — no execution — and the rollup is
scored against a device spec (peak FLOP/s + HBM bandwidth) to yield
per-op and whole-trace roofline step-time lower bounds:

    t_op >= max(flops / peak_flops, bytes / hbm_bw, comm_bytes / ici_bw)

An op whose arithmetic intensity (flops/byte) exceeds the device ridge
point (peak/bw) is *compute-bound*; below it, *memory-bound*. Matmuls at
LLM shapes sit far above the ridge; elementwise/reduction/shape ops sit far
below — which is why the roofline table, joined with measured device time
(``monitor.attribution_report``), says whether a slow op is worth a kernel
or a fusion fix (compute-bound: better MXU utilization; memory-bound: fuse
away the HBM round-trip).

Conventions (documented so golden tests are exact):

- matmul/linear: ``2·m·n·k`` FLOPs (multiply+add), bias adds counted.
- SDPA: two T×T matmuls = ``4·B·H·Tq·Tk·D`` plus 5 FLOPs per attention
  score for the online softmax; causal masks halve both. Flash-claimed
  SDPA reads only q/k/v and writes only out (+lse) — the T×T score matrix
  never touches HBM.
- elementwise: 1 FLOP per output element regardless of transcendence —
  they are bandwidth-bound on every spec in the table, so FLOP-weighting
  transcendentals would change no classification while making totals
  noisier against analytic estimates.
- reductions: 1 FLOP per *input* element (variance: 2).
- collectives: 0 FLOPs; ring-algorithm wire bytes — all_reduce moves
  ``2·(g−1)/g·nbytes``, all_gather/reduce_scatter ``(g−1)/g·nbytes``.
- pure layout ops (reshape/squeeze/broadcast): free — XLA fuses them;
  data-moving shape ops (transpose/cat/pad/take/...) are charged in+out
  bytes at 0 FLOPs.

Device peaks are datasheet numbers; override by passing your own
:class:`DeviceSpec` (docs/performance.md shows how to add a chip).
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Any, Callable, Optional, Sequence

from thunder_tpu.core.devices import TPU_SPECS, tpu_generation
from thunder_tpu.core.prims import OpTags, PrimIDs
from thunder_tpu.core.proxies import TensorProxy, pyval
from thunder_tpu.core.trace import TraceCtx

# =============================================================================
# Device specs
# =============================================================================


@dataclass(frozen=True)
class DeviceSpec:
    """Peak numbers for one chip. ``peak_flops`` maps a dtype class
    ("bf16" — the MXU path for f16/bf16, "f32", "int8") to FLOP/s;
    ``hbm_bw`` and ``ici_bw`` are bytes/s. Datasheet values — real kernels
    see less; the roofline is a *lower bound* on step time."""

    name: str
    peak_flops: dict[str, float]
    hbm_bw: float
    ici_bw: float = 0.0
    # Cross-slice (data-center network) wire bandwidth in bytes/s — the
    # second interconnect class of a federated mesh (ISSUE 18). An order of
    # magnitude below ICI on every real pod: collectives on the "dcn" mesh
    # axis (and the cross-slice leg of hier_all_reduce) price at this rate.
    # 0 means no DCN tier: cross-slice traffic falls back to ici_bw.
    dcn_bw: float = 0.0
    # Per-chip HBM capacity in bytes (datasheet; the runtime reserves a
    # fraction — analysis/liveness.device_capacity_bytes prefers the live
    # backend's bytes_limit and the THUNDER_TPU_HBM_BYTES override). 0 means
    # unknown: the liveness planner's fit checks are skipped.
    hbm_bytes: float = 0.0
    # Effective per-collective-family wire bandwidth (bytes/s), fitted from a
    # measured per-collective table via :func:`calibrate_ici`. Datasheet
    # ``ici_bw`` is the link rate; real collectives see less (latency,
    # algorithm inefficiency — ~1000× less on an emulated CPU mesh, where
    # "wire" time is thread rendezvous). None = uncalibrated: price at the
    # datasheet rate.
    ici_class_bw: Optional[dict] = None

    def peak_for(self, dtype: Any) -> float:
        return self.peak_flops.get(_dtype_class(dtype), self.peak_flops["bf16"])

    def ici_bw_for(self, cls: Optional[str]) -> float:
        """Wire bandwidth used to price a collective of HLO family ``cls``
        (``all-gather``/``all-reduce``/...): the calibrated per-class rate
        when one was fitted, else the datasheet ``ici_bw``."""
        if cls and self.ici_class_bw:
            bw = self.ici_class_bw.get(cls)
            if bw:
                return float(bw)
        return self.ici_bw

    @property
    def dcn_bw_or_ici(self) -> float:
        """The rate DCN-tier wire bytes price at: ``dcn_bw`` when the spec
        has a DCN class, else ``ici_bw`` (single-interconnect specs)."""
        return self.dcn_bw or self.ici_bw

    def ridge(self, dtype: Any) -> float:
        """Arithmetic intensity (FLOP/byte) at which compute and memory
        time are equal — ops above it are compute-bound."""
        return self.peak_for(dtype) / self.hbm_bw


def _dtype_class(dtype: Any) -> str:
    nbytes = getattr(dtype, "bytes", 4)
    if getattr(dtype, "kind", "float") in ("int", "uint", "bool"):
        return "int8" if nbytes <= 1 else "f32"
    return "bf16" if nbytes <= 2 else "f32"


def _tpu_bf16(gen: str) -> float:
    return TPU_SPECS[gen].peak_bf16_tflops * 1e12  # the bf16 peak has one home: core/devices.py


# Datasheet peaks. f32 on TPU runs through the MXU at roughly half bf16
# throughput (XLA splits f32 matmuls); "cpu" is a deliberately small spec so
# host-platform tests still classify sensibly.
# dcn_bw: per-chip share of the data-center network between slices — NIC
# line rate divided across the host's chips, an order of magnitude (or two)
# below ICI everywhere. These drive the federated-mesh roofline (ISSUE 18),
# not any single-slice number.
DEVICE_SPECS: dict[str, DeviceSpec] = {
    "v5e": DeviceSpec("v5e", {"bf16": _tpu_bf16("v5e"), "f32": 98.5e12, "int8": 394e12},
                      hbm_bw=819e9, ici_bw=186e9, dcn_bw=6.25e9, hbm_bytes=16e9),
    "v5p": DeviceSpec("v5p", {"bf16": _tpu_bf16("v5p"), "f32": 229.5e12, "int8": 918e12},
                      hbm_bw=2765e9, ici_bw=600e9, dcn_bw=25e9, hbm_bytes=95e9),
    "v4": DeviceSpec("v4", {"bf16": _tpu_bf16("v4"), "f32": 137.5e12, "int8": 275e12},
                     hbm_bw=1228e9, ici_bw=300e9, dcn_bw=6.25e9, hbm_bytes=32e9),
    "v6e": DeviceSpec("v6e", {"bf16": _tpu_bf16("v6e"), "f32": 459e12, "int8": 1836e12},
                      hbm_bw=1640e9, ici_bw=448e9, dcn_bw=12.5e9, hbm_bytes=32e9),
    "a100": DeviceSpec("a100", {"bf16": 312e12, "f32": 19.5e12, "int8": 624e12},
                       hbm_bw=1555e9, ici_bw=600e9, dcn_bw=25e9, hbm_bytes=80e9),
    # Host RAM is not a fixed datasheet number; 0 = capacity unknown, so the
    # liveness fit checks defer to memory_stats / THUNDER_TPU_HBM_BYTES.
    "cpu": DeviceSpec("cpu", {"bf16": 2e11, "f32": 2e11, "int8": 4e11},
                      hbm_bw=5e10, ici_bw=1e10, dcn_bw=1e9, hbm_bytes=0.0),
}


def collective_sym_class(sym_name: str) -> Optional[str]:
    """HLO collective family ("all-gather"/"all-reduce"/...) of a trace-level
    collective symbol name, or None. One authoritative sym→family map,
    shared with the measured half (observability/attribution.py)."""
    from thunder_tpu.observability.attribution import COLLECTIVE_SYM_CLASS

    return COLLECTIVE_SYM_CLASS.get(sym_name)


def calibrate_ici(spec: DeviceSpec, samples: Sequence[tuple]) -> DeviceSpec:
    """Fit an effective per-class ICI bandwidth from measured collectives.

    ``samples``: ``(cls, comm_bytes, measured_s)`` rows — the cost model's
    ring-factor wire bytes for a collective joined with its measured device
    time. The fit is the aggregate rate per family, ``Σ bytes / Σ seconds``,
    clamped to the datasheet ``ici_bw`` from above (a measurement can only
    reveal the wire to be *slower* than the link rate). Returns a new spec
    whose :meth:`DeviceSpec.ici_bw_for` prices each family at its fitted
    rate — the order-of-magnitude correction the comm scheduler's placement
    decisions need on meshes whose collective cost is rendezvous-dominated
    (the emulated CPU mesh measures ~1000× the datasheet wire time)."""
    import dataclasses

    by_cls: dict[str, list[float]] = {}
    for cls, comm_bytes, measured_s in samples:
        if not cls or not comm_bytes or not measured_s or measured_s <= 0:
            continue
        agg = by_cls.setdefault(str(cls), [0.0, 0.0])
        agg[0] += float(comm_bytes)
        agg[1] += float(measured_s)
    fitted = {
        cls: min(b / s, spec.ici_bw) if spec.ici_bw else b / s
        for cls, (b, s) in by_cls.items()
        if s > 0 and b > 0
    }
    if not fitted:
        return spec
    return dataclasses.replace(spec, ici_class_bw=fitted)


def resolve_device_spec(device: Any = None) -> DeviceSpec:
    """A :class:`DeviceSpec` from a spec object, a table name, or None
    (autodetect: the ``cpu`` spec, by name, when the local platform is cpu —
    the analysis tests price traces there — else the chip from
    ``core.devices.tpu_generation()``, the one ``device_kind`` lookup of
    the program). A device that is in neither table raises,
    named or autodetected: a guessed peak makes every bound wrong."""
    if isinstance(device, DeviceSpec):
        return device
    if isinstance(device, str):
        spec = DEVICE_SPECS.get(device.lower())
        if spec is None:
            raise ValueError(
                f"unknown device spec {device!r}; known: {sorted(DEVICE_SPECS)} "
                "(pass a DeviceSpec to add a chip)"
            )
        return spec
    import jax

    if jax.devices()[0].platform == "cpu":
        return DEVICE_SPECS["cpu"]
    return DEVICE_SPECS[tpu_generation()]


# =============================================================================
# Per-op cost rules
# =============================================================================


@dataclass
class OpCost:
    """Static cost of one BoundSymbol. ``bytes_moved`` is HBM traffic
    (reads + writes); ``comm_bytes`` is TOTAL interconnect wire traffic, of
    which ``dcn_bytes`` crosses the cross-slice DCN tier (ISSUE 18) and
    prices at :attr:`DeviceSpec.dcn_bw` instead of ICI."""

    flops: float = 0.0
    bytes_moved: float = 0.0
    comm_bytes: float = 0.0
    dcn_bytes: float = 0.0
    kind: str = "other"

    @property
    def arithmetic_intensity(self) -> float:
        return self.flops / self.bytes_moved if self.bytes_moved else float("inf")


def _tensor_args(bsym) -> list[TensorProxy]:
    return [p for p in bsym.flat_proxy_args if isinstance(p, TensorProxy)]


def _tensor_outs(bsym) -> list[TensorProxy]:
    return [p for p in bsym.flat_proxy_outs if isinstance(p, TensorProxy)]


def _numel(shape: Sequence[Any]) -> int:
    n = 1
    for s in shape:
        v = pyval(s)
        n *= int(v) if v is not None else int(s)
    return n


def _io_bytes(bsym) -> float:
    return float(sum(p.size_bytes for p in _tensor_args(bsym))
                 + sum(p.size_bytes for p in _tensor_outs(bsym)))


def _out_numel(bsym) -> int:
    return sum(p.numel for p in _tensor_outs(bsym))


def _in_numel(bsym) -> int:
    return sum(p.numel for p in _tensor_args(bsym))


# Bookkeeping prims with no runtime cost at all.
_FREE_IDS = {
    PrimIDs.DEL, PrimIDs.RETURN, PrimIDs.COMMENT, PrimIDs.PRINT,
    PrimIDs.UNPACK_TRIVIAL, PrimIDs.UNPACK_SEQUENCE, PrimIDs.UNPACK_KEY,
    PrimIDs.UNPACK_ATTR, PrimIDs.UNPACK_DIM,
    PrimIDs.CHECK_TENSOR_SHAPE_AND_METADATA, PrimIDs.CHECK_NUMBER_TYPE_AND_VALUE,
    PrimIDs.CHECK_STRING_VALUE, PrimIDs.CHECK_LEN, PrimIDs.CHECK_KEYS,
    PrimIDs.CHECK_NONE, PrimIDs.CHECK_DIM_BUCKET,
    PrimIDs.SHALLOW_COPY, PrimIDs.STOP_GRADIENT, PrimIDs.OPTIMIZATION_BARRIER, PrimIDs.ITEM,
}

# Layout-only ops XLA compiles away (no data movement charged).
_LAYOUT_IDS = {PrimIDs.RESHAPE, PrimIDs.SQUEEZE, PrimIDs.BROADCAST_IN_DIM}

# Data-moving shape ops: 0 FLOPs, in+out bytes.
_MOVE_IDS = {
    PrimIDs.TRANSPOSE, PrimIDs.CAT, PrimIDs.PAD, PrimIDs.SLICE, PrimIDs.FLIP,
    PrimIDs.TAKE, PrimIDs.TAKE_ALONG_AXIS, PrimIDs.GATHER, PrimIDs.SETITEM,
    PrimIDs.INDEX_PUT, PrimIDs.TENSOR_FROM_SEQUENCE, PrimIDs.DEVICE_PUT,
    PrimIDs.CONVERT_ELEMENT_TYPE, PrimIDs.COPY_, PrimIDs.TENSOR_CONSTANT,
}

# 2-FLOP-per-input-element reductions (mean+var in one pass).
_VAR_IDS = {PrimIDs.VAR, PrimIDs.VAR_MEAN}

_SDPA_FWD_IDS = {"torch.scaled_dot_product_attention", "torch.sdpa_fwd_res"}
_SDPA_BWD_IDS = {"torch.sdpa_bwd", "torch.sdpa_bwd_res"}

# Ring-collective wire-traffic factors as a function of group size g.
_COLLECTIVE_FACTORS: dict[str, Callable[[int], float]] = {
    "all_reduce": lambda g: 2.0 * (g - 1) / g,
    "all_gather": lambda g: (g - 1) / g,
    "reduce_scatter": lambda g: (g - 1) / g,
    "broadcast": lambda g: (g - 1) / g,
    "all_to_all": lambda g: (g - 1) / g,
    "ppermute": lambda g: 1.0,
    "mask_to_rank": lambda g: 0.0,
    "synchronize": lambda g: 0.0,
    "wait": lambda g: 0.0,
}


def _matmul_cost(bsym) -> OpCost:
    # out (..., m, n) = a (..., m, k) @ b (..., k, n): 2·m·n·k per batch.
    a = _tensor_args(bsym)[0]
    k = int(pyval(a.shape[-1]) or a.shape[-1])
    return OpCost(flops=2.0 * _out_numel(bsym) * k, bytes_moved=_io_bytes(bsym), kind="matmul")


def _linear_cost(bsym) -> OpCost:
    # out (..., n) = a (..., k) @ w.T (k, n) [+ bias]: 2·m·n·k + bias adds.
    tas = _tensor_args(bsym)
    a = tas[0]
    k = int(pyval(a.shape[-1]) or a.shape[-1])
    out_n = _out_numel(bsym)
    flops = 2.0 * out_n * k
    if len(tas) > 2:  # bias present
        flops += out_n
    return OpCost(flops=flops, bytes_moved=_io_bytes(bsym), kind="matmul")


def _conv_cost(bsym, *, bwd: bool = False) -> OpCost:
    # out numel × 2 × (cin/groups · ∏kernel); backward does ~2× the work
    # (grad-input + grad-weight each cost one forward).
    tas = _tensor_args(bsym)
    w = tas[1]
    k_work = _numel(w.shape[1:])  # cin/groups · ∏kernel
    flops = 2.0 * _out_numel(bsym) * k_work * (2.0 if bwd else 1.0)
    return OpCost(flops=flops, bytes_moved=_io_bytes(bsym), kind="matmul")


def _sdpa_dims(bsym) -> tuple[float, float, float, float, float, bool]:
    tas = _tensor_args(bsym)
    q, k = tas[0], tas[1]
    b = _numel(q.shape[:-2])  # B·H (grouped-query: q carries the full H)
    tq = int(pyval(q.shape[-2]) or q.shape[-2])
    tk = int(pyval(k.shape[-2]) or k.shape[-2])
    d = int(pyval(q.shape[-1]) or q.shape[-1])
    causal = bool(pyval(bsym.kwargs.get("is_causal", False)) or
                  any(a is True for a in bsym.args if isinstance(a, bool)))
    return b, tq, tk, d, 0.5 if causal else 1.0, causal


def _sdpa_cost(bsym, *, bwd: bool = False) -> OpCost:
    b, tq, tk, d, frac, _ = _sdpa_dims(bsym)
    # QKᵀ and AV: 2·(2·B·H·Tq·Tk·D); online softmax ≈ 5 FLOPs/score.
    flops = frac * (4.0 * b * tq * tk * d + 5.0 * b * tq * tk)
    if bwd:
        # dQ, dK, dV plus the flash re-descent of the forward ≈ 2.5× fwd.
        flops *= 2.5
    # Flash kernels never materialize the score matrix: HBM traffic is the
    # q/k/v/out (+residual) tensors only — exactly the proxy operands.
    return OpCost(flops=flops, bytes_moved=_io_bytes(bsym), kind="sdpa")


# The mesh axis whose hops cross slice boundaries (parallel/mesh.DCN_AXIS;
# the literal avoids importing jax-adjacent modules into the cost model).
_DCN_AXIS = "dcn"


def _collective_axis(bsym) -> Optional[str]:
    """The (first) mesh-axis operand of a collective bsym, when it is a
    string — the axis-aware bandwidth selection key (ISSUE 18)."""
    axis = bsym.args[1] if len(bsym.args) > 1 else bsym.kwargs.get("axis")
    return axis if isinstance(axis, str) else None


def _hier_all_reduce_cost(bsym) -> OpCost:
    """Wire bytes of the hierarchical all-reduce (dist_prims.hier_all_reduce):
    in-slice reduce-scatter + all-gather move ``2·(g_in−1)/g_in·nbytes``
    over ICI; the cross-slice all-reduce moves ``2·(g_out−1)/g_out`` of the
    1/g_in SHARD over DCN — the whole point of the lowering."""
    nbytes = float(sum(p.size_bytes for p in _tensor_args(bsym)))
    args = list(bsym.args) + [bsym.kwargs.get(k) for k in ()]
    g_in = args[3] if len(args) > 3 else bsym.kwargs.get("inner_size", 1)
    g_out = args[4] if len(args) > 4 else bsym.kwargs.get("outer_size", 1)
    g_in = int(pyval(g_in) or 1)
    g_out = int(pyval(g_out) or 1)
    ici = 2.0 * (g_in - 1) / g_in * nbytes if g_in > 1 else 0.0
    shard = nbytes / max(1, g_in)
    dcn = 2.0 * (g_out - 1) / g_out * shard if g_out > 1 else 0.0
    return OpCost(comm_bytes=ici + dcn, dcn_bytes=dcn, kind="collective")


def _collective_cost(bsym) -> OpCost:
    name = bsym.sym.name
    if name == "hier_all_reduce":
        return _hier_all_reduce_cost(bsym)
    factor_fn = _COLLECTIVE_FACTORS.get(name)
    nbytes = float(sum(p.size_bytes for p in _tensor_args(bsym)))
    on_dcn = _collective_axis(bsym) == _DCN_AXIS
    if factor_fn is None:
        return OpCost(comm_bytes=nbytes, dcn_bytes=nbytes if on_dcn else 0.0,
                      kind="collective")
    g = 1
    for a in bsym.flat_args:
        v = pyval(a)
        if isinstance(v, int) and not isinstance(v, bool) and v > 1:
            g = v
            break
    # Gather-type ops consume the SHARD but the ring moves (g-1)/g of the
    # FULL tensor — the output. This covers `synchronize` on a sharded fsdp
    # param (trace-level all-gather; the replicated passthrough keeps its
    # zero factor since out == in) so the overlap report's predicted column
    # prices the dominant FSDP collective instead of calling it free.
    if name in ("all_gather", "synchronize"):
        out = bsym.output
        out_bytes = float(getattr(out, "size_bytes", 0.0) or 0.0)
        if out_bytes > nbytes:
            wire = (g - 1) / g * out_bytes
            return OpCost(comm_bytes=wire, dcn_bytes=wire if on_dcn else 0.0,
                          kind="collective")
    wire = factor_fn(g) * nbytes
    return OpCost(comm_bytes=wire, dcn_bytes=wire if on_dcn else 0.0,
                  kind="collective")


def bsym_cost(bsym) -> Optional[OpCost]:
    """Static cost of one BoundSymbol, or None for pure bookkeeping
    (unpacks, guards, del/return). Dispatches on the prim id, the
    executor-claimed symbol id (SDPA family), and the COMM_OP tag."""
    sid = bsym.sym.id
    if sid in _FREE_IDS:
        return None
    if OpTags.COMM_OP in bsym.sym.tags:
        return _collective_cost(bsym)
    if isinstance(sid, str):
        if sid in _SDPA_FWD_IDS:
            return _sdpa_cost(bsym)
        if sid in _SDPA_BWD_IDS:
            return _sdpa_cost(bsym, bwd=True)
    if sid is PrimIDs.MATMUL:
        return _matmul_cost(bsym)
    if sid is PrimIDs.LINEAR:
        return _linear_cost(bsym)
    if sid is PrimIDs.CONVOLUTION:
        return _conv_cost(bsym)
    if sid is PrimIDs.CONVOLUTION_BWD:
        return _conv_cost(bsym, bwd=True)
    if sid in (PrimIDs.EMBEDDING, PrimIDs.EMBEDDING_BACKWARD):
        return OpCost(bytes_moved=_io_bytes(bsym), kind="gather")
    if sid in _LAYOUT_IDS:
        return OpCost(kind="layout")
    if sid in _MOVE_IDS:
        return OpCost(bytes_moved=_io_bytes(bsym), kind="shape")
    if not _tensor_outs(bsym):
        return None
    tags = bsym.sym.tags
    if OpTags.REDUCTION_OP in tags or sid in _VAR_IDS or sid in (
        PrimIDs.SUM, PrimIDs.PROD, PrimIDs.AMAX, PrimIDs.AMIN,
        PrimIDs.ARGMAX, PrimIDs.ARGMIN, PrimIDs.VAR, PrimIDs.VAR_MEAN,
        PrimIDs.CUMSUM, PrimIDs.CUMPROD,
    ):
        mult = 2.0 if sid in _VAR_IDS else 1.0
        return OpCost(flops=mult * _in_numel(bsym), bytes_moved=_io_bytes(bsym),
                      kind="reduction")
    if sid in (PrimIDs.SORT, PrimIDs.ARGSORT, PrimIDs.TOPK):
        return OpCost(flops=float(_in_numel(bsym)), bytes_moved=_io_bytes(bsym),
                      kind="sort")
    if sid in (PrimIDs.FULL, PrimIDs.IOTA, PrimIDs.UNIFORM, PrimIDs.RANDN,
               PrimIDs.UNIFORM_KEYED, PrimIDs.RANDN_KEYED, PrimIDs.UNIFORM_PHILOX):
        return OpCost(
            flops=float(_out_numel(bsym)),
            bytes_moved=float(sum(p.size_bytes for p in _tensor_outs(bsym))),
            kind="fill",
        )
    # Elementwise (and the unknown-op fallback): 1 FLOP per output element.
    kind = "elementwise" if (
        OpTags.ELEMENTWISE_UNARY_OP in tags or OpTags.ELEMENTWISE_BINARY_OP in tags
        or sid is PrimIDs.WHERE
    ) else "other"
    return OpCost(flops=float(_out_numel(bsym)), bytes_moved=_io_bytes(bsym), kind=kind)


# =============================================================================
# Trace rollup + roofline
# =============================================================================


@dataclass
class OpCostRow:
    """One trace line's cost, scored against the device spec."""

    index: int
    sym: str
    kind: str
    flops: float
    bytes_moved: float
    comm_bytes: float
    roofline_s: float
    bound: str  # "compute" | "memory" | "comm" | "free"
    intensity: float
    line: str = ""


@dataclass
class TraceCost:
    """Cost rollup of one trace against one device spec."""

    device: DeviceSpec
    rows: list[OpCostRow] = field(default_factory=list)
    total_flops: float = 0.0
    total_bytes: float = 0.0
    total_comm_bytes: float = 0.0
    # DCN-tier portion of total_comm_bytes: bytes a federated mesh moves
    # across the slice boundary (the "dcn" axis), priced at dcn_bw.
    total_dcn_bytes: float = 0.0
    # Σ flops/peak at each op's OWN dtype peak (accumulated by trace_cost so
    # the pure-compute bound agrees with the per-row roofline terms — a
    # bf16 trace must not be scored at the f32 peak here).
    _compute_s: float = 0.0

    @property
    def roofline_s(self) -> float:
        """Step-time lower bound with no cross-op fusion: Σ per-op bounds."""
        return sum(r.roofline_s for r in self.rows)

    @property
    def compute_s(self) -> float:
        """Pure-compute bound (every byte free), at per-op dtype peaks."""
        return self._compute_s

    @property
    def memory_s(self) -> float:
        """Pure-bandwidth bound (every FLOP free)."""
        return self.total_bytes / self.device.hbm_bw

    @property
    def comm_s(self) -> float:
        """Pure-wire bound: in-slice traffic at ICI bandwidth plus the
        DCN-tier portion at the spec's DCN class (0 when the trace has no
        collectives or the spec has no ICI)."""
        if not self.total_comm_bytes or not self.device.ici_bw:
            return 0.0
        ici = self.total_comm_bytes - self.total_dcn_bytes
        return ici / self.device.ici_bw + self.total_dcn_bytes / self.device.dcn_bw_or_ici

    def collective_rows(self) -> list[OpCostRow]:
        """The trace's collective ops — the predicted half of the
        compute–comm overlap report (observability/attribution.py joins
        these against measured hidden/exposed wire time)."""
        return [r for r in self.rows if r.kind == "collective"]

    def by_kind(self) -> dict[str, dict]:
        out: dict[str, dict] = {}
        for r in self.rows:
            d = out.setdefault(r.kind, {"flops": 0.0, "bytes": 0.0, "roofline_s": 0.0, "ops": 0})
            d["flops"] += r.flops
            d["bytes"] += r.bytes_moved
            d["roofline_s"] += r.roofline_s
            d["ops"] += 1
        return out

    def mfu_at(self, measured_s: float) -> float:
        """Model FLOPs utilization if the trace ran once in ``measured_s``."""
        return self.total_flops / measured_s / self.device.peak_flops["bf16"] if measured_s else 0.0

    def top(self, k: int = 10) -> list[OpCostRow]:
        return sorted(self.rows, key=lambda r: r.roofline_s, reverse=True)[:k]

    def format(self, top_k: int = 10) -> str:
        dev = self.device
        lines = [
            f"cost model [{dev.name}: {dev.peak_flops['bf16'] / 1e12:.0f} bf16 TFLOP/s, "
            f"{dev.hbm_bw / 1e9:.0f} GB/s HBM]",
            f"  total: {self.total_flops / 1e9:.3f} GFLOP, "
            f"{self.total_bytes / 1e6:.2f} MB moved"
            + (f", {(self.total_comm_bytes - self.total_dcn_bytes) / 1e6:.2f} MB on ICI" if self.total_comm_bytes else "")
            + (f", {self.total_dcn_bytes / 1e6:.2f} MB on DCN" if self.total_dcn_bytes else ""),
            f"  roofline step-time bound: {self.roofline_s * 1e3:.3f} ms unfused "
            f"(compute {self.compute_s * 1e3:.3f} ms, memory {self.memory_s * 1e3:.3f} ms)",
            f"  {'line':>5} {'sym':<28} {'kind':<12} {'GFLOP':>10} {'MB':>9} "
            f"{'AI':>8} {'bound':>8} {'us':>9}",
        ]
        for r in self.top(top_k):
            ai = f"{r.intensity:.1f}" if r.intensity != float("inf") else "inf"
            lines.append(
                f"  L{r.index:>4} {r.sym:<28.28} {r.kind:<12} {r.flops / 1e9:>10.4f} "
                f"{r.bytes_moved / 1e6:>9.3f} {ai:>8} {r.bound:>8} {r.roofline_s * 1e6:>9.1f}"
            )
        kinds = self.by_kind()
        if kinds:
            lines.append("  by kind: " + ", ".join(
                f"{k}={v['roofline_s'] * 1e6:.0f}us/{v['ops']}ops"
                for k, v in sorted(kinds.items(), key=lambda kv: -kv[1]["roofline_s"])
            ))
        return "\n".join(lines)

    def __str__(self) -> str:
        return self.format()


def trace_cost(trace: TraceCtx, device: Any = None) -> TraceCost:
    """Roll :func:`bsym_cost` up over ``trace`` and score each op against
    ``device`` (a :class:`DeviceSpec`, a name from ``DEVICE_SPECS``, or
    None to autodetect the local chip)."""
    dev = resolve_device_spec(device)
    tc = TraceCost(device=dev)
    for i, bsym in enumerate(trace.bound_symbols):
        c = bsym_cost(bsym)
        if c is None:
            continue
        outs = _tensor_outs(bsym)
        dtype = outs[0].dtype if outs else None
        t_compute = c.flops / dev.peak_for(dtype)
        t_memory = c.bytes_moved / dev.hbm_bw
        ici_bw = dev.ici_bw_for(collective_sym_class(bsym.sym.name)) if c.comm_bytes else 0.0
        if ici_bw and c.comm_bytes:
            # Price the two wire classes separately: in-slice bytes at the
            # (family-fitted) ICI rate, cross-slice bytes at the DCN rate.
            t_comm = (c.comm_bytes - c.dcn_bytes) / ici_bw
            t_comm += c.dcn_bytes / dev.dcn_bw_or_ici
        else:
            t_comm = 0.0
        t = max(t_compute, t_memory, t_comm)
        if t == 0.0:
            bound = "free"
        elif t == t_comm:
            bound = "comm"
        elif t == t_compute:
            bound = "compute"
        else:
            bound = "memory"
        tc.rows.append(OpCostRow(
            index=i, sym=bsym.sym.name, kind=c.kind, flops=c.flops,
            bytes_moved=c.bytes_moved, comm_bytes=c.comm_bytes,
            roofline_s=t, bound=bound, intensity=c.arithmetic_intensity,
            line=bsym.one_line(),
        ))
        tc.total_flops += c.flops
        tc.total_bytes += c.bytes_moved
        tc.total_comm_bytes += c.comm_bytes
        tc.total_dcn_bytes += c.dcn_bytes
        tc._compute_s += t_compute
    return tc


def cost_report(fn: Callable, *args, executors: Any = None, device: Any = None,
                **kwargs) -> TraceCost:
    """Trace ``fn`` on the example inputs through the pass pipeline the
    dispatcher runs (``thunder_tpu/pipeline.py``) and return the :class:`TraceCost`
    of the resulting execution trace — the static half of the attribution
    workflow (``examine.cost_report`` re-exports this; docs/performance.md).

    For an already-compiled ``thunder_tpu.jit`` function, the underlying
    function is traced (mirroring ``examine.lint``); to cost the exact
    trace an entry executed, call :func:`trace_cost` on
    ``compile_stats(jfn).last_traces[-1]`` instead."""
    from thunder_tpu import pipeline
    from thunder_tpu.api import trace_program
    from thunder_tpu.core.trace import debug_checks
    from thunder_tpu.extend import resolve_executors

    cd = getattr(fn, "_lc_cd", None)
    if cd is not None:
        fn = cd.fn
    with debug_checks(False):
        _, comp = trace_program(fn, args, kwargs)
        extrace = pipeline.compile_trace(pipeline.clean(comp)[-1], resolve_executors(executors)).claimed
    return trace_cost(extrace, device)


# =============================================================================
# HLO-op pricing (the compiled-executable twin of bsym_cost)
# =============================================================================

# Ring-collective wire-traffic factors by HLO family name — the compiled-HLO
# counterpart of _COLLECTIVE_FACTORS (keyed by trace sym name above). The
# derived reduce-scatter (an all-reduce whose consumers all slice a shard,
# recovered by analysis/hlo_audit) prices at the reduce-scatter factor: the
# program provably needs only the scattered result.
HLO_COLLECTIVE_FACTORS: dict[str, Callable[[int], float]] = {
    "all-reduce": lambda g: 2.0 * (g - 1) / g,
    "all-gather": lambda g: (g - 1) / g,
    "reduce-scatter": lambda g: (g - 1) / g,
    "collective-broadcast": lambda g: (g - 1) / g,
    "all-to-all": lambda g: (g - 1) / g,
    "ragged-all-to-all": lambda g: (g - 1) / g,
    "collective-permute": lambda g: 1.0,
}


def hlo_collective_wire_bytes(family: str, full_bytes: float, group_size: int) -> float:
    """Ring wire traffic of one HLO collective: the family factor applied to
    the FULL tensor bytes (gather output / reduce input — the caller picks
    the full side, :func:`hlo_op_cost` does for parsed ops)."""
    factor_fn = HLO_COLLECTIVE_FACTORS.get(family)
    if factor_fn is None or group_size <= 1:
        return full_bytes if factor_fn is not None else 0.0
    return factor_fn(group_size) * full_bytes


# Opcode classes, mirroring the bsym conventions in the module docstring:
# layout-only ops are free (XLA fuses them), data movers are charged in+out
# bytes at 0 FLOPs, elementwise is 1 FLOP per output element, reductions
# 1 FLOP per input element. Call-like ops are free at the call site — their
# bodies are priced standalone (or folded into the fusion) by the auditor.
_HLO_FREE_OPS = frozenset({
    "parameter", "constant", "iota", "bitcast", "bitcast-convert", "reshape",
    "broadcast", "get-tuple-element", "tuple", "after-all", "partition-id",
    "replica-id", "domain", "opt-barrier", "while", "call", "conditional",
    "custom-call", "rng-get-and-update-state", "get-dimension-size",
    "add-dependency", "token",
})
_HLO_MOVE_OPS = frozenset({
    "slice", "dynamic-slice", "dynamic-update-slice", "concatenate", "pad",
    "gather", "transpose", "reverse", "copy", "copy-start", "copy-done",
    "send", "recv", "send-done", "recv-done", "infeed", "outfeed",
})
_HLO_REDUCE_OPS = frozenset({"reduce", "reduce-window", "scatter", "sort", "select-and-scatter"})


def hlo_op_cost(op: Any, *, inner_flops: float = 0.0) -> Optional[OpCost]:
    """Static cost of one parsed HLO instruction — the HLO-op → FLOPs/HBM/ICI
    rules the auditor (analysis/hlo_audit.py) prices every compiled op with.

    ``op`` is duck-typed (:class:`~thunder_tpu.analysis.hlo_audit.HloOp`):
    ``opcode``, ``result_bytes``/``result_numel``, ``operand_bytes``/
    ``operand_numel``, ``group_size``, ``k_dim`` (dot/conv contraction size),
    ``family`` (collective family after classification, None otherwise).
    ``inner_flops`` carries a fusion body's summed FLOPs — the fusion is
    charged its boundary bytes plus the body's arithmetic, and the body's
    ops are NOT priced standalone (hlo_audit skips fusion-called
    computations). Returns None for `-done` completion halves (their
    `-start` op carries the cost)."""
    opcode = op.opcode
    fam = getattr(op, "family", None) or (
        opcode[:-6] if opcode.endswith("-start") and opcode[:-6] in HLO_COLLECTIVE_FACTORS
        else opcode if opcode in HLO_COLLECTIVE_FACTORS else None
    )
    if fam is not None:
        if opcode.endswith("-done"):
            return None
        # The ring moves (g−1)/g of the FULL tensor: the gathered output for
        # all-gather (result is full), the reduced input for a native
        # reduce-scatter (operand is full); all-reduce and the derived
        # reduce-scatter have out == in == full.
        full = op.operand_bytes if opcode.startswith("reduce-scatter") else op.result_bytes
        return OpCost(
            comm_bytes=hlo_collective_wire_bytes(fam, full, max(1, int(op.group_size))),
            kind="collective",
        )
    io = op.operand_bytes + op.result_bytes
    if opcode == "fusion":
        return OpCost(flops=inner_flops, bytes_moved=io, kind="fusion")
    if opcode == "dot":
        return OpCost(flops=2.0 * op.result_numel * max(1.0, op.k_dim),
                      bytes_moved=io, kind="matmul")
    if opcode == "convolution":
        return OpCost(flops=2.0 * op.result_numel * max(1.0, op.k_dim),
                      bytes_moved=io, kind="matmul")
    if opcode in _HLO_FREE_OPS:
        return None
    if opcode in _HLO_MOVE_OPS:
        return OpCost(bytes_moved=io, kind="layout" if opcode.startswith("copy") else "shape")
    if opcode in _HLO_REDUCE_OPS:
        return OpCost(flops=op.operand_numel, bytes_moved=io, kind="reduction")
    # Everything else prices as elementwise: 1 FLOP per output element.
    return OpCost(flops=op.result_numel, bytes_moved=io, kind="elementwise")
