"""The executor framework: pluggable backends claiming trace symbols.

Reference parity: thunder/extend/__init__.py (`Executor:47`,
`OperatorExecutor:190`, `FusionExecutor:132`, `ImplInfo:32`,
`register_executor:275`, default/always registries `:268-388`,
optimization fuel `:136-155`).

Executors are priority-ordered: the claiming pass
(thunder_tpu/executors/passes.py) hands each bound symbol to the first
executor whose checker accepts it, descending into subsymbols when no
executor claims a composite op. On TPU the terminal executor is the JAX/XLA
operator executor (thunder_tpu/executors/jaxex.py) — "fusion" is XLA staging
the whole claimed trace under one jit — while Pallas kernels register as
higher-priority operator executors taking the cuDNN/Triton/TE seats.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Optional, Sequence

from thunder_tpu.core.baseutils import check
from thunder_tpu.core.symbol import BoundSymbol, Symbol


@dataclass
class ImplInfo:
    """Reference parity: thunder/extend/__init__.py `ImplInfo:32`."""

    symbol: Optional[Symbol] = None  # executor-specific op symbol, if any
    fn: Optional[Callable] = None  # concrete implementation
    checker: Optional[Callable] = None  # (*args, **kwargs) -> bool
    execution_transform: Optional[Callable] = None  # (*args, **kwargs) -> result, records ops
    grad_transform: Optional[Callable] = None  # custom VJP rule


class Executor:
    def __init__(self, name: str, *, version: str = "0.1"):
        self.name = name
        self.version = version
        self.implmap: dict[Any, ImplInfo] = {}
        # Optimization fuel for bisecting claiming/fusion bugs
        # (reference: extend/__init__.py:136-155).
        self._fuel: Optional[int] = None

    def __repr__(self) -> str:
        return f"Executor({self.name!r})"

    # -- fuel ----------------------------------------------------------------

    def set_fuel(self, n: Optional[int]) -> None:
        self._fuel = n

    def get_fuel(self, amount: int = 1) -> bool:
        if self._fuel is None:
            return True
        if self._fuel >= amount:
            self._fuel -= amount
            return True
        return False

    # -- claiming ------------------------------------------------------------

    def accepts(self, bsym: BoundSymbol) -> bool:
        """What the checker says of ``bsym``; spends no fuel, so a transform
        may ask before it rewrites for this executor."""
        info = self.implmap.get(bsym.sym.id)
        if info is None:
            return False
        if info.checker is not None:
            try:
                if not info.checker(*bsym.args, **bsym.kwargs):
                    return False
            except Exception:
                return False
        return True

    def can_execute(self, bsym: BoundSymbol) -> bool:
        # When fuel is set, each claim consumes one unit; exhausting fuel
        # makes this executor stop claiming (bisection knob).
        return self.accepts(bsym) and self.get_fuel(1)

    def get_impl(self, sym_id: Any) -> Optional[Callable]:
        info = self.implmap.get(sym_id)
        if info is None:
            return None
        if info.fn is not None:
            return info.fn
        if info.symbol is not None and info.symbol.python_impl is not None:
            return info.symbol.python_impl
        return None

    def get_execution_transform(self, sym_id: Any) -> Optional[Callable]:
        info = self.implmap.get(sym_id)
        return info.execution_transform if info is not None else None

    def get_grad_transform(self, sym_id: Any) -> Optional[Callable]:
        info = self.implmap.get(sym_id)
        return info.grad_transform if info is not None else None


class OperatorExecutor(Executor):
    """Reference parity: thunder/extend/__init__.py `OperatorExecutor:190`."""

    def register_operator(
        self,
        name: str,
        *,
        meta: Callable,
        fn: Callable,
        tags: Sequence[Any] = (),
        replaces: Optional[Any] = None,
    ) -> Symbol:
        """Create an executor-owned symbol with a concrete implementation
        (reference: `register_operator:203`)."""
        sym = Symbol(
            name,
            meta,
            id=f"{self.name}.{name}",
            is_prim=True,
            tags=tags,
            executor=self,
            python_impl=fn,
            module=self.name,
        )
        self.implmap[sym.id] = ImplInfo(symbol=sym, fn=fn)
        if replaces is not None:
            self.implmap[replaces] = ImplInfo(symbol=sym, fn=fn)
        return sym

    def register_implementation(
        self,
        sym_or_id: Symbol | Any,
        *,
        op: Optional[Symbol] = None,
        fn: Optional[Callable] = None,
        checker: Optional[Callable] = None,
        execution_transform: Optional[Callable] = None,
        grad_transform: Optional[Callable] = None,
    ) -> None:
        """Map an IR symbol to this executor (reference: `register_implementation:247`)."""
        sym_id = sym_or_id.id if isinstance(sym_or_id, Symbol) else sym_or_id
        impl_fn = fn if fn is not None else (op.python_impl if op is not None else None)
        self.implmap[sym_id] = ImplInfo(
            symbol=op,
            fn=impl_fn,
            checker=checker,
            execution_transform=execution_transform,
            grad_transform=grad_transform,
        )


class FusionExecutor(Executor):
    """An executor that rewrites whole regions (reference: `FusionExecutor:132`).

    On TPU, XLA is the fusion engine and runs below the operator executors;
    this class remains for regional executors (e.g. an explicitly-partitioned
    Pallas megakernel or a torch.compile-on-CPU region) and for API parity.
    """

    def fusion_pass(self, trace):
        raise NotImplementedError

    def register_temporary_operation(self, name: str, fn: Callable) -> Symbol:
        sym = Symbol(name, None, id=f"{self.name}.{name}", executor=self, python_impl=fn, module=self.name)
        self.implmap[sym.id] = ImplInfo(symbol=sym, fn=fn)
        return sym


# -- global registry ----------------------------------------------------------

_executor_map: dict[str, Executor] = {}
_default_executors: list[Executor] = []
_always_executors: list[Executor] = []


def register_executor(ex: Executor) -> Executor:
    _executor_map[ex.name] = ex
    return ex


def get_executor(name: str) -> Optional[Executor]:
    return _executor_map.get(name)


def get_all_executors() -> tuple[Executor, ...]:
    return tuple(_executor_map.values())


def get_default_executors() -> tuple[Executor, ...]:
    return tuple(_default_executors)


def get_always_executors() -> tuple[Executor, ...]:
    return tuple(_always_executors)


def add_default_executor(ex: Executor, *, front: bool = True) -> None:
    if ex in _default_executors:
        _default_executors.remove(ex)
    if front:
        _default_executors.insert(0, ex)
    else:
        _default_executors.append(ex)


def add_always_executor(ex: Executor) -> None:
    if ex not in _always_executors:
        _always_executors.append(ex)


def resolve_executors(executors: Optional[Sequence[Executor | str]]) -> tuple[Executor, ...]:
    if executors is None:
        return get_default_executors()
    out: list[Executor] = []
    for e in executors:
        if isinstance(e, Executor):
            out.append(e)
        else:
            ex = get_executor(e)
            check(ex is not None, lambda: f"Unknown executor {e!r}")
            out.append(ex)
    return tuple(out)


# -- lookasides ---------------------------------------------------------------

_lookasides: dict[Callable, Callable] = {}


def register_lookaside(fn: Callable, replacement: Callable) -> None:
    """Map an external callable to a traceable replacement
    (reference: extend/__init__.py `register_lookaside:391`)."""
    _lookasides[fn] = replacement


def get_lookaside(fn: Callable) -> Optional[Callable]:
    return _lookasides.get(fn)
