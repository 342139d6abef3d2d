"""Who writes and who reads each array of a trace, and the two shapes of line
that the layout passes (``attention_layout.py``, ``ssm_layout.py``) look
through on their way from a consumer back to the projection that made its
operand: a slice of the last dimension, and a ``permute``'s or ``reshape``'s
trailing arguments."""

from __future__ import annotations

from thunder_tpu.core.proxies import Proxy, TensorProxy
from thunder_tpu.core.pytree import tree_flatten
from thunder_tpu.core.trace import TraceCtx


class Uses:
    """Who writes and who reads each proxy of a trace, by index."""

    def __init__(self, trc: TraceCtx):
        self.bsyms = trc.bound_symbols
        self.writer: dict[str, int] = {}
        self.readers: dict[str, list[int]] = {}
        for i, b in enumerate(self.bsyms):
            for p in b.flat_proxy_outs:
                self.writer[p.name] = i
            for name in dict.fromkeys(p.name for p in b.flat_proxy_args):
                self.readers.setdefault(name, []).append(i)
        self.returned = {p.name for p in tree_flatten(trc.output)[0] if isinstance(p, Proxy)}

    def made_by(self, p, sym_id: str, readers: int = 1):
        """The index of the ``sym_id`` that wrote ``p`` as its one output, if
        ``p`` has just ``readers`` readers and does not leave the trace."""
        if not isinstance(p, TensorProxy) or p.name in self.returned:
            return None
        i = self.writer.get(p.name)
        if i is None or self.bsyms[i].sym.id != sym_id or self.bsyms[i].output is not p:
            return None
        return i if len(self.readers.get(p.name, ())) == readers else None


def dims(rest):
    """A ``permute``'s or ``reshape``'s trailing arguments as one sequence, given as one or spread."""
    return rest[0] if len(rest) == 1 and isinstance(rest[0], (tuple, list)) else rest


def last_dim_slice(uses: Uses, s):
    """``s`` as ``lin[..., a:b]``: (lin, a, b, the index), or None."""
    i = uses.made_by(s, "torch.getitem")
    if i is None:
        return None
    lin, key = uses.bsyms[i].args
    key = key if isinstance(key, tuple) else (key,)
    *lead, last = key
    whole = lambda k: k is Ellipsis or (isinstance(k, slice) and k == slice(None))
    if not (all(whole(k) for k in lead) and isinstance(last, slice) and last.step in (None, 1)
            and (Ellipsis in lead or len(key) == len(lin.shape))):
        return None
    a, b, _ = last.indices(lin.shape[-1])
    return lin, a, b, i
