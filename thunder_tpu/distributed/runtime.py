"""Staging traces with explicit collectives onto a device mesh.

A trace containing ``dist_prims`` collectives references mesh axes by name;
this module stages its compiled callable inside ``shard_map`` over a
``jax.sharding.Mesh`` so those names resolve, then ``jax.jit``s the result —
one SPMD executable per host, collectives riding ICI/DCN.

Reference analogue: the runtime seat of the generated code calling
`torch_all_gather_prim_impl` → NCCL (thunder/executors/torchex.py:1709-1729)
— except the program is compiled once and the comm/compute overlap is XLA's
latency-hiding scheduler rather than stream juggling.
"""

from __future__ import annotations

from typing import Callable


def shard_map_callable(fn: Callable, mesh, in_specs, out_specs, *, check_vma: bool = False,
                       trace_lines=None, schedule=None) -> Callable:
    """Wrap a pure callable in shard_map over ``mesh`` and jit it.

    The result routes through the collective watchdog
    (``resilience/watchdog.guard_call``) whenever a timeout is configured
    (``THUNDER_TPU_COLLECTIVE_TIMEOUT_S`` / ``watchdog.configure``): a
    shard_map program IS a collective dispatch site, so a peer that stops
    participating raises a typed ``CollectiveTimeoutError`` (naming
    ``trace_lines`` when the caller has them) instead of hanging the host
    forever. Unconfigured, the wrapper is one dict probe per call."""
    import jax

    from thunder_tpu.resilience import watchdog

    inner = jax.shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=check_vma)
    return watchdog.wrap(
        jax.jit(inner),
        fn_name=getattr(fn, "__name__", "shard_map"),
        trace_lines=trace_lines,
        schedule=schedule,
    )


def compile_with_collectives(
    fn: Callable,
    example_args: tuple,
    mesh,
    in_specs,
    out_specs,
    *,
    grad: bool = False,
):
    """Trace ``fn`` through the framework pipeline (so dist_prims record into
    the trace), then stage the claimed trace under shard_map over ``mesh``.

    Returns the jitted callable (flat args in trace order) and the claimed
    trace.
    """
    from functools import partial

    from thunder_tpu import pipeline
    from thunder_tpu.api import trace_program
    from thunder_tpu.extend import resolve_executors
    from thunder_tpu.transforms.autodiff import grad_transform

    _, comp = trace_program(fn, example_args, {})
    extrace = pipeline.compile_trace(
        pipeline.clean(comp)[-1], resolve_executors(None),
        transforms=(partial(grad_transform, return_value=True),) if grad else (),
    ).claimed
    return stage_collective_trace(extrace, mesh, in_specs, out_specs), extrace


def stage_collective_trace(extrace, mesh, in_specs, out_specs) -> Callable:
    """Stage an already-claimed collective-bearing execution trace under
    shard_map over ``mesh`` (the tail of :func:`compile_with_collectives`,
    split out so callers holding a transformed trace can restage it
    without re-tracing)."""
    from thunder_tpu.api import keyed_callable
    from thunder_tpu.distributed.prims import collective_trace_lines

    inner = keyed_callable(extrace)
    # Certify the collective schedule (ISSUE 10): stamps the per-axis order
    # baseline on the trace and hands the watchdog the certified order so a
    # timeout names the collectives that must already have completed before
    # the pending one. Advisory — certification failure never blocks staging.
    schedule = None
    try:
        from thunder_tpu.analysis import schedule as sched_mod

        schedule = sched_mod.stamp(extrace).axis_labels()
    except Exception:  # noqa: BLE001
        pass
    return shard_map_callable(
        inner, mesh, in_specs, out_specs,
        trace_lines=collective_trace_lines(extrace),
        schedule=schedule,
    )
