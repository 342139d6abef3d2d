"""Attention's operands head-major from the projection that makes them.

Programs write attention token-major (``models/gpt.py::_attention`` does, as
litgpt- and HF-style programs do): one ``linear`` for q, k and v, three slices
of its last dimension, a ``reshape`` to (B, T, heads, hs) and a ``permute`` to
(B, heads, T, hs) each, ``apply_rope`` on q and k, the attention call, and a
``permute`` and ``reshape`` back before the output ``linear``. On the chip each
slice and permute is a copy of an activation, and the softmax scale is a pass
over q of its own: 168 copies and 24 scalings in a forward call of pythia-410m,
a sixth of its time, with no arithmetic in them (PERF.md, PR 30).

XLA writes a projection head-major with no copy, but only where the dot's own
output has the head dimension. So this pass rewrites the idiom to the packed
form: ``linear_heads`` writes (B, H + 2G, T, hs) in one dot on the weight as
it lies; ``apply_rope_heads`` reads q's and k's heads out of that array by its
block index, and writes q times the softmax scale; the attention call takes
``scale=1.0``. Heads narrower than the 128 lanes (pythia's 64) would cost the
dot twice its time in half-empty tiles, so there the projection lays two side
by side, (B, (H + 2G) / 2, T, 128), and the kernels that read it give each
head a (T, hs) of its own: ``split_heads`` does that for v, which is a plain
slice where the heads fill the lanes. Where only q comes through ``linear ->
reshape -> permute -> apply_rope`` (latent attention) it does that part.

The idiom is matched by what the trace shows, in each spelling of it (PR 39):
between the permute and the attention call q and k may pass through a
``rms_norm`` over each head's features (LFM2, Trinity: token-major that norm
costs a float32 copy of q and three passes more), through ``apply_rope``,
through both in that order, and q and k alike; the call may be
``scaled_dot_product_attention``, ``window_attention`` or, since PR 41,
``linear_attention`` (MiniCPM-SALA's linear layers: as many key heads as query
heads, q and k normed and roped). ``apply_rope_heads`` takes the norm's weight
and tables that may be absent, and does in one pass what the site has. A site
with neither norm nor rope is left alone. A new line keeps the region of the
line it stands for: the attention call alone carries the call's.

The softmax calls take ``scale=1.0`` and q's head call the scale. Linear
attention has no softmax to move a scale through: it applies its scale to its
float32 output, and goes on doing so. Its call is written again as it stood, on
the new q, k and v, with its ``decay``, ``scale`` and ``chunk``; both head
calls take 1.0.

Forward programs only: in a trace that holds a backward q, k and v have a
second reader and nothing matches. It asks the checkers first, about the lines
that somebody has to take for the rewrite to pay (``_CLAIMED_BY``): where
``pallas`` would not take the rope call or ``flash`` a softmax call (a CPU run
without the kernels), the program stays as written; a window call may be
``pallas``'s own kernel's or splash's, by its shapes. A consumer need not be
claimed: linear attention runs as XLA's decomposition, and the pass asks
nobody to take it. Each new symbol keeps the program as written as its
decomposition, so a claim that fails later still computes it.
"""

from __future__ import annotations

import math
import time
from typing import NamedTuple, Optional

from thunder_tpu.core.proxies import TensorProxy, pyval, variableify
from thunder_tpu.core.trace import TraceCtx, from_trace, tracectx, wrap_in_trace_provenance
from thunder_tpu.executors.pallasex import heads_per_lane_group
from thunder_tpu.executors.passes import would_claim
from thunder_tpu.transforms.attention_residuals import _bound_sdpa
from thunder_tpu.transforms.uses import Uses, dims, last_dim_slice

FOLDED_TAG = "attention_layouts_folded"  # how many attention sites the pass rewrote

_SDPA = "torch.scaled_dot_product_attention"
_WINDOW = "torch.window_attention"
_LINEAR = "torch.linear_attention"
_CONSUMERS = (_SDPA, _WINDOW, _LINEAR)
_HEADS_FIRST = (0, 2, 1, 3)
# who has to take each new line for the rewrite to pay: asked of the checkers before anything is changed. Linear
# attention is XLA's decomposition, which nobody claims, so it is not asked about.
_CLAIMED_BY = {"torch.apply_rope_heads": ("pallas",), "torch.split_heads": ("pallas",), _SDPA: ("flash",),
               _WINDOW: ("pallas", "flash")}


def _token_major(uses: Uses, p):
    """``p`` (B, h, T, hs) as ``permute(reshape(s, (B, T, h, hs)), (0, 2, 1, 3))``:
    (s, the two indices), or None."""
    perm = uses.made_by(p, "torch.permute")
    if perm is None or tuple(dims(uses.bsyms[perm].args[1:])) != _HEADS_FIRST:
        return None
    mid = uses.bsyms[perm].args[0]
    resh = uses.made_by(mid, "torch.reshape")
    if resh is None:
        return None
    s = uses.bsyms[resh].args[0]
    B, h, T, hs = p.shape
    if tuple(mid.shape) != (B, T, h, hs) or tuple(getattr(s, "shape", ())) != (B, T, h * hs):
        return None
    return s, [perm, resh]


def _bound_call(call) -> dict:
    """An attention call's q, k and v under ``_bound_sdpa``'s names, whichever of the three it is."""
    if call.sym.id == _SDPA:
        return _bound_sdpa(call.args, call.kwargs)
    q, k, v = call.args[:3]
    return dict(query=q, key=k, value=v, attn_mask=None, dropout_p=0.0, scale=call.kwargs.get("scale"))


class _Steps(NamedTuple):
    """What stands between a projection and the attention call on q or on k."""

    src: TensorProxy        # the projection, or the slice of it, that the heads are cut from
    how: dict               # ``apply_rope_heads``'s cos and sin (None: no rope) and, where normed, norm_weight and eps
    region: Optional[str]   # of the innermost step, which the new line keeps
    gone: list              # the indices the new line stands for

    def asked(self) -> set:
        return {key for key, a in self.how.items() if a is not None}


def _head_steps(uses: Uses, p) -> Optional[_Steps]:
    """``p`` (B, h, T, hs) as ``apply_rope(rms_norm(x, (hs,), weight, eps), cos, sin)``,
    either step or both left out, each read by the next alone and x token-major
    out of a projection; None where it is anything else, or neither."""
    from thunder_tpu.torch import RMS_NORM_EPS

    how, gone = dict(cos=None, sin=None), []
    if (i := uses.made_by(p, "torch.apply_rope")) is not None:
        p, how["cos"], how["sin"] = uses.bsyms[i].args
        gone.append(i)
    if (i := uses.made_by(p, "torch.rms_norm")) is not None:
        norm = dict(zip(("a", "normalized_shape", "weight", "eps"), uses.bsyms[i].args), **uses.bsyms[i].kwargs)
        p, weight, hs = norm["a"], norm.get("weight"), p.shape[-1]
        if tuple(norm["normalized_shape"]) != (hs,) or tuple(getattr(weight, "shape", ())) != (hs,):
            return None
        eps = norm.get("eps")
        how.update(norm_weight=weight, eps=RMS_NORM_EPS if eps is None else float(pyval(eps)))
        gone.append(i)
    if not gone or (tm := _token_major(uses, p)) is None:
        return None
    return _Steps(tm[0], how, uses.bsyms[gone[-1]].region, gone + tm[1])


def _match(uses: Uses, b: dict):
    """What of the idiom stands in front of an attention call: the operands of
    the rewrite and the indices that go, or None.

    Packed: q, k and v are the three slices that tile one ``linear``'s output,
    q and k through the same steps. Else q alone, straight from a ``linear``
    of its own."""
    q, k, v = b["query"], b["key"], b["value"]
    if (sq := _head_steps(uses, q)) is None:
        return None
    lin_at = uses.made_by(sq.src, "torch.linear")
    if lin_at is not None:
        return dict(lin=lin_at, q=sq, gone=[*sq.gone, lin_at])

    sk, tv = _head_steps(uses, k), _token_major(uses, v)
    if sk is None or tv is None or sq.asked() != sk.asked():
        return None
    slices = [last_dim_slice(uses, s) for s in (sq.src, sk.src, tv[0])]
    if any(s is None for s in slices) or len({s[0].name for s in slices}) != 1:
        return None
    lin = slices[0][0]
    lin_at = uses.made_by(lin, "torch.linear", readers=3)
    (H, hs), G = (q.shape[1], q.shape[3]), k.shape[1]
    tiles = [(s[1], s[2]) for s in slices] == [(0, H * hs), (H * hs, (H + G) * hs), ((H + G) * hs, lin.shape[-1])]
    if lin_at is None or not tiles or tuple(v.shape) != tuple(k.shape):
        return None
    return dict(lin=lin_at, q=sq, k=sk, v_region=uses.bsyms[tv[1][0]].region,
                gone=[*sq.gone, *sk.gone, *tv[1], *(s[3] for s in slices), lin_at])


def _rewritten(trc: TraceCtx, uses: Uses, at: int, b: dict, m: dict) -> list:
    """The lines that take the attention call's place, each in the region of
    the line it stands for."""
    import thunder_tpu.torch as ltorch

    call = uses.bsyms[at]
    q, k, v = b["query"], b["key"], b["value"]
    x, w, *bias = uses.bsyms[m["lin"]].args
    H, G = q.shape[1], k.shape[1]
    if call.sym.id == _LINEAR:  # its scale is on the float32 output, and stays in the call
        scale = 1.0
    else:
        scale = float(pyval(b["scale"])) if b["scale"] is not None else 1.0 / math.sqrt(q.shape[-1])

    def heads(packed, steps: _Steps, first, count, scale, split):
        trc.region, how = steps.region, dict(steps.how)
        return ltorch.apply_rope_heads(packed, how.pop("cos"), how.pop("sin"), first, count, scale, split, **how)

    with tracectx(trc):
        region = trc.region
        trc.push_scope(lines := [])
        try:
            split = 1
            trc.region = uses.bsyms[m["lin"]].region
            if "k" in m:  # packed: k and v come out of the same projection
                split = heads_per_lane_group(q.shape[-1], H, G)
                packed = ltorch.linear_heads(x, w, *bias, heads=(H + 2 * G) // split)
                k = heads(packed, m["k"], H, G, 1.0, split)
                trc.region = m["v_region"]
                v = ltorch.split_heads(packed, H + G, G, split) if split > 1 else packed[:, H + G:]
            else:
                packed = ltorch.linear_heads(x, w, *bias, heads=H)
            q = heads(packed, m["q"], 0, H, scale, split)
            trc.region = call.region
            if call.sym.id == _SDPA:
                y = ltorch.scaled_dot_product_attention(q, k, v, is_causal=b["is_causal"], scale=1.0,
                                                        enable_gqa=b["enable_gqa"])
            elif call.sym.id == _WINDOW:
                y = ltorch.window_attention(q, k, v, window=call.kwargs["window"], scale=1.0)
            else:  # decay, scale and chunk as the program gave them
                y = ltorch.linear_attention(q, k, v, *call.args[3:], **call.kwargs)
        finally:
            trc.pop_scope()
            trc.region = region
    # the result under the name its readers know
    lines[-1] = lines[-1].from_bsym_swap_proxies({variableify(y): call.output})
    return lines


def fold_attention_layouts(trc: TraceCtx, executors) -> TraceCtx:
    """Forward-trace pass. Counts the sites it rewrote under ``trc.tags[FOLDED_TAG]``."""
    trc.tags[FOLDED_TAG] = 0
    executors = tuple(executors or ())
    names = {getattr(e, "name", None) for e in executors}
    ids = {str(b.sym.id) for b in trc.bound_symbols}
    if "pallas" not in names or not ids.intersection(_CONSUMERS) or any("_bwd" in i for i in ids):
        return trc
    start = time.perf_counter_ns()
    uses = Uses(trc)
    put: dict[int, list] = {}
    gone: set[int] = set()
    for at, call in enumerate(uses.bsyms):
        if call.sym.id not in _CONSUMERS:
            continue
        b = _bound_call(call)
        if b["attn_mask"] is not None or float(pyval(b["dropout_p"])) != 0.0:
            continue
        m = _match(uses, b)
        if m is None:
            continue
        lines = _rewritten(trc, uses, at, b, m)
        if any(would_claim(line, executors) not in _CLAIMED_BY[line.sym.id] for line in lines if line.sym.id in _CLAIMED_BY):
            continue
        put[at] = lines
        gone.update(m["gone"])

    if not put:
        return trc
    new = from_trace(trc)
    for i, bsym in enumerate(uses.bsyms):
        if i not in gone:
            new.bound_symbols.extend(put.get(i, (bsym,)))
    new.tags[FOLDED_TAG] = len(put)
    return wrap_in_trace_provenance(new, "Attention layout folding", start)
