"""The state-space mixer reads its packed arrays where they lie.

A Mamba-2 mixer (``models/gpt.py::_mamba``, as the published programs write it)
makes ``[z | xBC | dt]`` in one projection, cuts ``xBC`` out for the causal
convolution, and cuts the convolution's result ``[x | B | C]`` in three for
``ssm_scan``. On the chip two of those cuts are copies of an activation with no
arithmetic in them: ``pallas``'s scan is a custom call, whose operand lies whole
in HBM, so XLA writes x out in front of it; and the convolution pads its input,
which XLA does not fuse a slice through, so ``xBC`` is written out too. In
``granite-4.0-h-micro.fwd-t16k`` the two cost 30.8 ms of a 782.7 ms call, as
much as the 36 scans they stand in front of (PERF.md, PR 46). z and dt are read
out of the projection by the fusions that use them, at no cost.

One mechanism at both sites: a consumer that reads a last-dimension range of a
packed activation is handed the packed array and the range, instead of a copy
of the range. Where ``ssm_scan``'s x, B and C are ``reshape(xbc[..., a:b])`` of
one array, the three ranges tiling it in that order, ``xbc`` the result of
``causal_conv_silu`` and read by those three lines alone, the call becomes
``ssm_scan_packed(xbc, ...)``, which ``pallas`` reads by block index; and where
that convolution's input is itself ``lin[..., a:b]``, read by it alone, the
convolution takes ``lin`` and ``columns=(a, b)`` and cuts each tap out of the
padded whole. Each new line is in the region of the line it stands for, and
its decomposition is the program as written: a claim that fails later, a CPU
run and the trace VJP compute what the program wrote.

Forward programs only, and only where ``pallas`` would take the packed call
(asked of its checker before anything is changed: the dtype, the head size, the
chunk, and that B's and C's columns are whole lane groups that x's are a
multiple of). Anything else stays as written, and ``ssm_scan``'s own claim on
three arrays stands.

Then the packed array exists only to be written by the convolution and read
back once by the scan (PERF.md: 38 ms of a 750 ms call, and 10 GB to HBM
and back). Where the convolution takes its projection's columns and
``pallas`` would take the two as one call (asked of its checker: the taps look
back at most 7 rows, and x's and B's columns lie at whole blocks of the
projection), the ranged convolution and the packed scan become one
``ssm_conv_scan`` line in the scan's region, bound over those two lines, which
convolves each chunk where it scans it. Where the checker declines, the two
lines stand.
"""

from __future__ import annotations

import time

from thunder_tpu.core.proxies import variableify
from thunder_tpu.core.trace import TraceCtx, from_trace, tracectx, wrap_in_trace_provenance
from thunder_tpu.executors.passes import would_claim
from thunder_tpu.transforms.uses import Uses, last_dim_slice

FOLDED_TAG = "ssm_layouts_folded"  # how many scans the pass handed their packed array; on a trace that holds a scan
FUSED_TAG = "ssm_convs_fused"  # how many of those convolve in the scan's call; beside FOLDED_TAG

_SCAN = "torch.ssm_scan"
_CONV = "torch.causal_conv_silu"


_SCAN_ARGS = ("x", "dt", "A", "B", "C", "D", "chunk")
_CONV_ARGS = ("x", "w", "bias", "columns")
_PACKED_ARGS = ("xbc", "dt", "A", "D", "heads", "groups", "state", "chunk")


def _bound(bsym, names) -> dict:
    """A line's arguments under their names, given by position or by keyword; None for one left out."""
    return {**dict.fromkeys(names), **dict(zip(names, bsym.args)), **bsym.kwargs}


def _match(uses: Uses, b: dict):
    """What of the idiom stands in front of a scan: the packed array, the
    convolution that wrote it, that convolution's own slice (or None) and the
    indices of the three slices and their reshapes; None where x, B and C are
    not the three parts of one convolution's result, in that order, each read
    by its own line alone."""
    cuts, cut_at = [], []
    for p in (b["x"], b["B"], b["C"]):
        at = uses.made_by(p, "torch.reshape")
        if at is None or len(p.shape) != 4:
            return None
        s = uses.bsyms[at].args[0]
        cut = last_dim_slice(uses, s)
        if cut is None or tuple(s.shape) != (*p.shape[:2], p.shape[2] * p.shape[3]):
            return None
        cuts.append(cut)
        cut_at += [at, cut[3]]
    xbc = cuts[0][0]
    (H, P), (G, N) = b["x"].shape[2:], b["B"].shape[2:]
    tiles = [0, H * P, H * P + G * N, xbc.shape[-1]]
    conv_at = uses.made_by(xbc, _CONV, readers=3)
    if (conv_at is None or any(cut[0].name != xbc.name for cut in cuts) or tuple(b["C"].shape[2:]) != (G, N)
            or [(cut[1], cut[2]) for cut in cuts] != list(zip(tiles, tiles[1:]))):
        return None
    conv = _bound(uses.bsyms[conv_at], _CONV_ARGS)
    taken = last_dim_slice(uses, conv["x"]) if conv["columns"] is None else None
    return dict(xbc=xbc, conv_at=conv_at, conv=conv, taken=taken, cut=sorted(cut_at))


def _packed_scan(uses: Uses, call, b: dict, m: dict):
    """``ssm_scan_packed`` on the packed array in ``call``'s place, bound over
    the lines it stands for: the three slices, their reshapes and ``call`` are
    its decomposition as they lie in the trace (traced anew through the symbol,
    36 decompositions of ``ssm_scan`` cost as much as tracing the model)."""
    import thunder_tpu.torch as ltorch

    (H, _), (G, N) = b["x"].shape[2:], b["B"].shape[2:]
    line = ltorch.ssm_scan_packed._symbol.bind(
        m["xbc"], b["dt"], b["A"], b["D"], heads=H, groups=G, state=N, chunk=b["chunk"],
        output=call.output, subsymbols=(*(uses.bsyms[i] for i in m["cut"]), call))
    line.region = call.region
    return line


def _ranged_conv(trc: TraceCtx, uses: Uses, m: dict):
    """The convolution on its projection's columns, in the region of the line
    it stands for and writing the array that line wrote."""
    import thunder_tpu.torch as ltorch

    (lin, lo, hi, _), conv = m["taken"], m["conv"]
    with tracectx(trc):
        outer = trc.region
        trc.push_scope(lines := [])
        try:
            trc.region = uses.bsyms[m["conv_at"]].region
            xbc = ltorch.causal_conv_silu(lin, conv["w"], conv["bias"], columns=(lo, hi))
        finally:
            trc.pop_scope()
            trc.region = outer
    (line,) = lines
    return line.from_bsym_swap_proxies({variableify(xbc): m["xbc"]})


def _conv_scan(conv, scan):
    """``ssm_conv_scan`` in the packed scan's place and region, bound over the
    ranged convolution and the packed scan, which are its decomposition."""
    import thunder_tpu.torch as ltorch

    c, s = _bound(conv, _CONV_ARGS), _bound(scan, _PACKED_ARGS)
    line = ltorch.ssm_conv_scan._symbol.bind(
        c["x"], c["w"], c["bias"], s["dt"], s["A"], s["D"], columns=c["columns"], heads=s["heads"],
        groups=s["groups"], state=s["state"], chunk=s["chunk"], output=scan.output, subsymbols=(conv, scan))
    line.region = scan.region
    return line


def fold_ssm_layouts(trc: TraceCtx, executors) -> TraceCtx:
    """Forward-trace pass. A trace that holds no ``ssm_scan`` comes back as it
    was; one that does says how many sites were rewritten under
    ``trc.tags[FOLDED_TAG]``, and how many of them convolve in the scan's call
    under ``trc.tags[FUSED_TAG]``."""
    ids = {str(b.sym.id) for b in trc.bound_symbols}
    if _SCAN not in ids:
        return trc
    trc.tags[FOLDED_TAG] = trc.tags[FUSED_TAG] = 0
    executors = tuple(executors or ())
    if "pallas" not in {getattr(e, "name", None) for e in executors} or any("_bwd" in i for i in ids):
        return trc
    start = time.perf_counter_ns()
    uses = Uses(trc)
    put: dict[int, object] = {}
    gone: set[int] = set()
    sites = fused = 0
    for at, call in enumerate(uses.bsyms):
        if call.sym.id != _SCAN:
            continue
        b = _bound(call, _SCAN_ARGS)
        m = _match(uses, b)
        if m is None:
            continue
        scan = _packed_scan(uses, call, b, m)
        if would_claim(scan, executors) != "pallas":
            continue
        put[at] = scan
        gone.update(m["cut"])
        sites += 1
        if m["taken"] is None:
            continue
        conv = _ranged_conv(trc, uses, m)
        gone.add(m["taken"][3])
        both = _conv_scan(conv, scan)
        if would_claim(both, executors) == "pallas":
            put[at] = both
            gone.add(m["conv_at"])
            fused += 1
        else:
            put[m["conv_at"]] = conv

    if not sites:
        return trc
    new = from_trace(trc)
    new.bound_symbols.extend(put.get(i, bsym) for i, bsym in enumerate(uses.bsyms) if i not in gone)
    new.tags[FOLDED_TAG], new.tags[FUSED_TAG] = sites, fused
    return wrap_in_trace_provenance(new, "State-space layout folding", start)
