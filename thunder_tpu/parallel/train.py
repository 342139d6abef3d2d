"""Sharded training steps: trace-compiled fw+bw staged under one pjit.

Reference parity: the end-to-end training loops of the reference's
benchmark/examples (thunder/benchmarks/benchmark_litgpt.py,
examples/lit-gpt/train_fsdp.py) — forward+backward through the compiler,
optimizer outside the trace (the reference leaves the optimizer to the user;
here it is a pure-jax AdamW *inside the same jit* so the whole step is one
XLA executable: fw, bw, grad reduction, and update fuse and overlap under
the latency-hiding scheduler, the TPU answer to `sort_waits` +
CUDAGraphExecutor).

All shardings are `NamedSharding`s over the caller's mesh; optimizer state
inherits the param specs, giving ZeRO-sharded optimizer states for free.
"""

from __future__ import annotations

import contextlib
import time
from functools import partial
from typing import Any, Optional

from thunder_tpu.core.pytree import tree_flatten, tree_map, tree_unflatten
from thunder_tpu.models.gpt import GPTConfig, loss_fn


# =============================================================================
# AdamW (pure jax, pytree-structured)
# =============================================================================


def adamw_init(params):
    import jax.numpy as jnp

    zeros = tree_map(lambda p: jnp.zeros_like(p), params)
    return {"step": jnp.zeros((), dtype=jnp.int32), "m": zeros, "v": tree_map(lambda p: jnp.zeros_like(p), params)}


def opt_state_specs(param_specs, optimizer: str = "adamw"):
    """PartitionSpec pytree for the optimizer state matching
    :func:`adamw_init`'s structure: moments inherit the param specs (ZeRO
    sharding for free), the step counter replicates. The elastic-resume
    path (``resilience/elastic.py``) reshards saved optimizer state through
    exactly these specs, so they live here next to the init."""
    from jax.sharding import PartitionSpec

    if optimizer == "sgd":
        return {"step": PartitionSpec()}
    return {"step": PartitionSpec(), "m": param_specs, "v": param_specs}


def adamw_update(params, grads, state, *, lr=1e-3, b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.0):
    import jax.numpy as jnp

    step = state["step"] + 1
    t = step.astype(jnp.float32)
    c1 = 1.0 - jnp.power(b1, t)
    c2 = 1.0 - jnp.power(b2, t)

    def upd(p, g, m, v):
        # Moments in the grad dtype (f32 grads → f32 moments).
        g = g.astype(m.dtype) if g.dtype != m.dtype else g
        m_new = b1 * m + (1.0 - b1) * g
        v_new = b2 * v + (1.0 - b2) * (g * g)
        update = (m_new / c1) / (jnp.sqrt(v_new / c2) + eps)
        if weight_decay:
            update = update + weight_decay * p.astype(update.dtype)
        return (p - lr * update.astype(p.dtype)), m_new, v_new

    flat_p, spec = tree_flatten(params)
    flat_g, _ = tree_flatten(grads)
    flat_m, _ = tree_flatten(state["m"])
    flat_v, _ = tree_flatten(state["v"])
    out = [upd(p, g, m, v) for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
    new_p = tree_unflatten(spec, [o[0] for o in out])
    new_m = tree_unflatten(spec, [o[1] for o in out])
    new_v = tree_unflatten(spec, [o[2] for o in out])
    return new_p, {"step": step, "m": new_m, "v": new_v}


# =============================================================================
# Sharded train step
# =============================================================================


@contextlib.contextmanager
def _phase(program, name: str):
    """One compile-phase span of the train path, through the recorder the
    ``jit`` path has (``api._record_compile_phase``, read back with
    ``thunder_tpu.compile_phases()``). The body may put extras into the dict
    it is handed; a stage that raises records nothing, as on the ``jit``
    path."""
    from thunder_tpu.api import _record_compile_phase

    extra: dict = {}
    t0 = time.perf_counter()
    yield extra
    _record_compile_phase(program, name, time.perf_counter() - t0, **extra)


def _compile_loss_and_grads(config: GPTConfig, params, idx, targets, executors=None):
    """Trace loss_fn through the framework pipeline → a pure jax callable
    taking the flat tensor leaves and returning (loss, grads_tuple).

    Claiming runs under the caller's ``kernel_mesh`` declaration, so the
    kernel checkers size their blocks on one batch shard's rows."""
    from thunder_tpu import pipeline
    from thunder_tpu.api import _record_compile_phase, keyed_callable, trace_program
    from thunder_tpu.extend import resolve_executors
    from thunder_tpu.observability.events import current_compile_id
    from thunder_tpu.transforms.autodiff import grad_transform

    # The phases carry the ``jit`` path's names (docs/observability.md,
    # "Compile-phase spans").
    program = current_compile_id()  # build_train_step's compile_scope
    fn = lambda p, i, t: loss_fn(p, i, t, config)  # noqa: E731
    with _phase(program, "trace"):
        _, comp = trace_program(fn, (params, idx, targets), {})
    t0 = time.perf_counter()
    comp = pipeline.clean(comp)[-1]
    clean_s = time.perf_counter() - t0
    compiled = pipeline.compile_trace(comp, resolve_executors(executors),
                                      transforms=(partial(grad_transform, return_value=True),))
    _record_compile_phase(program, "transforms", clean_s + compiled.seconds["transforms"],
                          **compiled.extras["transforms"])
    _record_compile_phase(program, "claim", compiled.seconds["claim"])
    with _phase(program, "codegen"):
        run = keyed_callable(compiled.claimed)
    return run, compiled.claimed


def build_train_step(
    config: GPTConfig,
    params,
    idx,
    targets,
    *,
    mesh=None,
    param_specs=None,
    batch_spec=None,
    lr: float = 3e-4,
    weight_decay: float = 0.1,
    b1: float = 0.9,
    b2: float = 0.95,
    grads_in_f32: bool = True,
    donate: bool = True,
    executors=None,
    optimizer: str = "adamw",
    return_extrace: bool = False,
):
    """Compile one full training step (fw+bw+AdamW) as a single sharded XLA
    executable. Returns ``(step_fn, opt_state)``;
    ``step_fn(params, opt_state, idx, targets) -> (params, opt_state, loss)``.

    ``return_extrace=True`` appends the claimed joint execution trace to the
    return tuple (``perfbench/jobs/train.py`` counts the claimed kernels in it).

    Under a ``mesh`` the step is one ``jax.jit`` with shardings, partitioned
    by XLA; the claimed Mosaic kernels, which the partitioner cannot split,
    run per batch shard inside ``jax.shard_map`` over the axes dim 0 of
    ``batch_spec`` names (executors/kernel_mesh.py).
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from thunder_tpu.api import _ensure_runtime
    from thunder_tpu.executors.kernel_mesh import kernel_mesh
    from thunder_tpu.observability.events import compile_scope
    from thunder_tpu.parallel.sharding import data_spec as _dspec

    _ensure_runtime()  # x64 dtype semantics + the persistent compile cache
    if mesh is not None and batch_spec is None:
        batch_spec = _dspec(mesh)
    batch_axes = batch_spec[0] if mesh is not None and len(batch_spec) else None

    def ns(spec_tree):
        return tree_map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, PartitionSpec))

    opt_sh = ns(opt_state_specs(param_specs, optimizer)) if mesh is not None else None

    # One program id a step, from the sequence the jit path's compile ids
    # come from: its compile-phase spans read like any other compile's.
    with compile_scope() as program:
        with kernel_mesh(mesh, batch_axes):
            loss_and_grads, extrace = _compile_loss_and_grads(
                config, params, idx, targets, executors=executors,
            )
        # The state goes in as the step hands it back (an int32 array, and
        # under a mesh laid out by ``opt_sh``): a first call whose arguments
        # differ in type or layout from the second's traces and compiles the
        # step twice.
        with _phase(program, "optimizer_state") as extra:
            opt_state = adamw_init(params) if optimizer != "sgd" else {"step": jnp.zeros((), dtype=jnp.int32)}
            if mesh is not None:
                opt_state = jax.device_put(opt_state, opt_sh)
            extra["leaves"] = len(tree_flatten(opt_state)[0])

    def step(params, opt_state, idx, targets):
        # This body runs only while jax traces it, so the span costs a step
        # nothing and puts nothing in the jaxpr: one record a trace.
        with _phase(program, "jax_trace"):
            flat, _ = tree_flatten(((params, idx, targets), {}))
            with kernel_mesh(mesh, batch_axes):  # read while jax.jit traces the kernels
                loss, grads = loss_and_grads(*flat)
            if grads_in_f32:
                grads = tuple(g.astype(jnp.float32) for g in grads)
            p_flat, p_spec = tree_flatten(params)
            grads_tree = tree_unflatten(p_spec, list(grads))
            if optimizer == "sgd":
                # bf16-true SGD(wd) — no moment state; what lets multi-GB models
                # train on one 16 GB chip
                new_params = tree_map(
                    lambda p, g: (p - lr * (g.astype(p.dtype) + weight_decay * p)).astype(p.dtype),
                    params, grads_tree,
                )
                return new_params, opt_state, loss
            new_params, new_state = adamw_update(
                params, grads_tree, opt_state, lr=lr, b1=b1, b2=b2, weight_decay=weight_decay
            )
            return new_params, new_state, loss

    # Donation metadata for the static planner suite (ISSUE 10): the param
    # leaves of the claimed trace are the donated buffers, so the liveness
    # planner frees them at last use, and the donation sanitizer rules
    # (analysis/rules.py donation.*) can check the SDC/rerun invariants
    # statically. donate_argnums=(0, 1) ALSO donates the optimizer state,
    # but the opt update is staged in the outer `step` jit, OUTSIDE the
    # claimed trace — opt leaves have no trace-level proxies to tag, so the
    # trace metadata covers exactly the donated buffers the trace can see
    # (params); the step-level invariant (SDC re-run needs the whole
    # previous state alive) is carried by _thunder_donates on the callable,
    # which run_training checks up front.
    if donate:
        from thunder_tpu.core.proxies import TensorProxy

        n_params = len(tree_flatten(params)[0])
        extrace.tags["donated_inputs"] = tuple(
            a.name for a in extrace.args[:n_params] if isinstance(a, TensorProxy)
        )

    def _stamp(jfn):
        try:
            jfn._thunder_donates = bool(donate)
        except Exception:  # jit wrapper without attribute support
            pass
        return jfn

    if mesh is None:
        jfn = _stamp(jax.jit(step, donate_argnums=(0, 1) if donate else ()))
        return (jfn, opt_state, extrace) if return_extrace else (jfn, opt_state)

    param_sh = ns(param_specs)
    data_sh = NamedSharding(mesh, batch_spec)
    loss_sh = NamedSharding(mesh, PartitionSpec())

    jfn = _stamp(jax.jit(
        step,
        in_shardings=(param_sh, opt_sh, data_sh, data_sh),
        out_shardings=(param_sh, opt_sh, loss_sh),
        donate_argnums=(0, 1) if donate else (),
    ))
    return (jfn, opt_state, extrace) if return_extrace else (jfn, opt_state)
