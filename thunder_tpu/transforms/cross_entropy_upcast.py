"""Cross-entropy on the logits as the head wrote them.

Programs compute logits in bfloat16 and call ``logits.float()`` before the loss
(``models/gpt.py::loss_fn`` does, as user programs do). In the joint trace
that is a ``convert_element_type`` to float32 whose only readers are one
``torch.cross_entropy`` and its ``torch.cross_entropy_bwd``: a float32 copy
of the largest activation of the step, written, read twice, kept from the
forward to the backward, and a float32 gradient of the same size that the
convert's own backward then rounds to bf16.

The ``pallas`` executor's kernels upcast each block in VMEM, which is exact,
and write the gradient in their input's dtype, which is that same rounding.
So where it claims both on the unconverted logits this pass hands them those:
the loss stays float32, the gradient comes out in the logits' dtype, both
converts go, and the shape-only operations between (the reshape to (B*T, V)
and its backward) apply to the narrow arrays. Where it declines (a CPU run
without the kernels, a vocabulary off the lane width, label smoothing, a
quarantined kernel) the program stays as written. The folded symbols keep
the program as written as their decomposition, so a claim that fails later
still computes it.
"""

from __future__ import annotations

import time

from thunder_tpu.core import dtypes, prims
from thunder_tpu.core.prims import PrimIDs
from thunder_tpu.core.proxies import Proxy, TensorProxy
from thunder_tpu.core.pytree import tree_flatten
from thunder_tpu.core.trace import TraceCtx, tracectx, wrap_in_trace_provenance
from thunder_tpu.executors.passes import would_claim

FOLDED_TAG = "cross_entropy_upcasts_folded"  # how many (forward, backward) pairs the pass folded

# What the match walks through and what it folds: the reshape to (B*T, V) of
# ``loss_fn`` and bfloat16, the one 16-bit dtype the kernels take.
_SHAPE_ONLY = (PrimIDs.RESHAPE,)
_NARROW = (dtypes.bfloat16,)


def _is_convert(bsym, to) -> bool:
    return bsym.sym.id is PrimIDs.CONVERT_ELEMENT_TYPE and dtypes.to_dtype(bsym.args[1]) in to


def _match(bsyms, readers, returned, up: int):
    """The pair that alone reads what convert ``bsyms[up]`` wrote, with the
    shape-only chains around it: (forward chain, ce, bwd, backward chain,
    the convert back) as indices, or None."""

    def only_reader(p):
        r = readers.get(p.name, ())
        return r[0] if len(r) == 1 and p.name not in returned else None

    def walk(p):  # through shape-only operations that alone read their input
        chain = []
        while (i := only_reader(p)) is not None and bsyms[i].sym.id in _SHAPE_ONLY and bsyms[i].args[0] is p:
            chain.append(i)
            p = bsyms[i].output
        return chain, p

    fw_chain, wide = walk(bsyms[up].output)
    pair = readers.get(wide.name, ())
    if len(pair) != 2 or wide.name in returned:
        return None
    ce, bwd = pair
    if (bsyms[ce].sym.id != "torch.cross_entropy" or bsyms[bwd].sym.id != "torch.cross_entropy_bwd"
            or not bsyms[ce].args or bsyms[ce].args[0] is not wide
            or len(bsyms[bwd].args) < 2 or bsyms[bwd].args[1] is not wide):
        return None
    bw_chain, grad = walk(bsyms[bwd].output)
    down = only_reader(grad)
    narrow = bsyms[up].args[0].dtype
    if down is None or not _is_convert(bsyms[down], (narrow,)) or bsyms[down].args[0] is not grad:
        return None
    return fw_chain, ce, bwd, bw_chain, down


def _narrowed(trc, bsyms, chain, p, last=None):
    """The shape-only ``chain`` rebound to start from ``p``, its outputs
    copies in ``p``'s dtype (the last one ``last``, where given); and the
    proxy it ends in."""
    out = []
    for n, i in enumerate(chain):
        with tracectx(trc):
            o = last if last is not None and n == len(chain) - 1 else TensorProxy(like=bsyms[i].output, dtype=p.dtype)
        out.append(bsyms[i].from_bsym(args=(p, *bsyms[i].args[1:]), output=o))
        p = o
    return out, p


def _as_written_bwd(trc, bwd, logits, grad) -> list:
    """The backward as written on narrow ``logits``, under names of its own:
    upcast, ``cross_entropy_bwd`` in float32, and the rounding into ``grad``."""
    import thunder_tpu.torch as ltorch

    with tracectx(trc):
        trc.push_scope(recorded := [])
        try:
            d = ltorch.cross_entropy_bwd(bwd.args[0], prims.convert_element_type(logits, dtypes.float32),
                                         *bwd.args[2:], **bwd.kwargs)
        finally:
            trc.pop_scope()
    return [*recorded, prims.convert_element_type.bind(d, logits.dtype, output=grad)]


def fold_cross_entropy_upcasts(trc: TraceCtx, executors) -> TraceCtx:
    """Joint-trace pass (forward and backward in one trace, as the ``grad``
    pipelines and ``build_train_step`` have it). Counts what it folded under
    ``trc.tags[FOLDED_TAG]``."""
    trc.tags[FOLDED_TAG] = 0
    executors = tuple(executors or ())
    bsyms = trc.bound_symbols
    if not (any(getattr(e, "name", None) == "pallas" for e in executors)
            and any(b.sym.id == "torch.cross_entropy_bwd" for b in bsyms)):
        return trc
    start = time.perf_counter_ns()
    readers: dict[str, list[int]] = {}
    for i, b in enumerate(bsyms):
        for name in dict.fromkeys(p.name for p in b.flat_proxy_args):
            readers.setdefault(name, []).append(i)
    returned = {p.name for p in tree_flatten(trc.output)[0] if isinstance(p, Proxy)}

    gone: set[int] = set()
    for up, conv in enumerate(bsyms):
        if not (_is_convert(conv, (dtypes.float32,)) and isinstance(conv.args[0], TensorProxy)
                and conv.args[0].dtype in _NARROW):
            continue
        found = _match(bsyms, readers, returned, up)
        if found is None:
            continue
        fw_chain, ce, bwd, bw_chain, down = found
        new_fw, logits = _narrowed(trc, bsyms, fw_chain, conv.args[0])
        with tracectx(trc):
            grad = bsyms[down].output if not bw_chain else TensorProxy(like=bsyms[bwd].output, dtype=logits.dtype)
        new_ce = bsyms[ce].from_bsym(args=(logits, *bsyms[ce].args[1:]))
        new_bwd = bsyms[bwd].from_bsym(args=(bsyms[bwd].args[0], logits, *bsyms[bwd].args[2:]), output=grad)
        if not (would_claim(new_ce, executors) == would_claim(new_bwd, executors) == "pallas"):
            continue
        # What each decomposes into, should its claim fail later: the program as written.
        wide = bsyms[ce].args[0]
        new_ce.subsymbols = (prims.convert_element_type.bind(logits, dtypes.float32, output=wide), bsyms[ce])
        new_bwd.subsymbols = tuple(_as_written_bwd(trc, bsyms[bwd], logits, grad))
        new_bw, _ = _narrowed(trc, bsyms, bw_chain, grad, last=bsyms[down].output)
        for i, b in zip((*fw_chain, ce, bwd, *bw_chain), (*new_fw, new_ce, new_bwd, *new_bw)):
            bsyms[i] = b
        gone.update((up, down))
        trc.tags[FOLDED_TAG] += 1

    if not gone:
        return trc
    bsyms[:] = [b for i, b in enumerate(bsyms) if i not in gone]
    return wrap_in_trace_provenance(trc, "Cross-entropy upcast folding", start)
