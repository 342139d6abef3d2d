"""Claimed Mosaic kernels under a device mesh.

XLA's SPMD partitioner cannot split a Mosaic custom call ("Mosaic kernels
cannot be automatically partitioned. Please wrap the call in a shard_map"),
so a step staged with ``jax.jit(in_shardings=...)`` runs each claimed kernel
inside ``jax.shard_map`` over the step's mesh. ``parallel.build_train_step``
declares that mesh, and the mesh axes its batch is split over, with
:func:`kernel_mesh` around claiming and staging; the kernel executors
(flashex, pallasex) route every ``pallas_call`` through
:func:`per_batch_shard`. With no mesh declared both are the identity, which is
also right for traces that are themselves staged under ``shard_map``
(distributed/runtime.py): there the kernels already see local shards.

The contract is the declarer's: dim 0 of every operand a claimed kernel
takes is the batch (or batch*time rows), and it divides by the product of
the batch axes. Mesh axes the batch is not split over (``tp``, ``sp``, ...)
see the kernel replicated: the partitioner gathers what it needs at the
shard_map boundary. Correct for any mesh; heads-over-``tp`` and
sequence-over-``sp`` kernels are ROADMAP S8's.
"""

from __future__ import annotations

import contextlib
import contextvars

_DECLARED = contextvars.ContextVar("thunder_tpu_kernel_mesh", default=None)  # (mesh, batch_axes)


@contextlib.contextmanager
def kernel_mesh(mesh, batch_axes):
    """Declare ``mesh`` (None: no mesh) and the axis names dim 0 is split over."""
    if isinstance(batch_axes, str):
        batch_axes = (batch_axes,)
    token = _DECLARED.set(None if mesh is None else (mesh, tuple(batch_axes or ())))
    try:
        yield
    finally:
        _DECLARED.reset(token)


def batch_shards() -> int:
    """How many shards dim 0 is split into (1 with no mesh declared) — the
    checkers size their blocks on the per-shard row count."""
    declared = _DECLARED.get()
    if declared is None:
        return 1
    mesh, axes = declared
    n = 1
    for a in axes:
        n *= int(mesh.shape[a])
    return n


def per_batch_shard(fn, *args, replicated=()):
    """``fn(*args)``; under a declared mesh, inside ``jax.shard_map`` with dim
    0 of every argument (except the positions in ``replicated``) and of every
    result split over the batch axes."""
    declared = _DECLARED.get()
    if declared is None:
        return fn(*args)
    import jax
    from jax.sharding import PartitionSpec as P

    mesh, axes = declared
    rows = P(axes) if axes else P()
    in_specs = tuple(P() if i in replicated else rows for i in range(len(args)))
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=rows, check_vma=False)(*args)
