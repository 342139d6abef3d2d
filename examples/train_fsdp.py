"""Sharded pretraining example (reference: examples/lit-gpt/train_fsdp.py).

Where the reference wraps the model in torch FSDP and lets NCCL shard
params/grads, the thunder_tpu way is a device mesh + PartitionSpecs: params
are dim-0 sharded over the ``fsdp`` axis (and optionally Megatron-split over
``tp``), the batch is split over ``dp``×``fsdp``, and XLA's SPMD partitioner
inserts and schedules every collective. Optimizer state inherits the param
specs — ZeRO-sharded AdamW for free.

Run on real hardware (mesh axes = however many chips you have):
    python examples/train_fsdp.py --mesh fsdp=8
    python examples/train_fsdp.py --mesh dp=2,fsdp=2,tp=2 --model llama-2-7b

Functional check on 8 virtual CPU devices (what tests/test_examples.py does;
the result line names the device, and a CPU time is not a speed):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/train_fsdp.py --mesh fsdp=8 --model llama-tiny --iters 4

The claimed Pallas kernels run per batch shard inside ``jax.shard_map`` (the
partitioner cannot split a Mosaic call); ``build_train_step`` arranges it.
``main`` returns what it measured, and ``chip_smoke.py`` calls it.

Multi-host: launch one process per host with the usual JAX env
(``thunder_tpu.distributed.init()`` wires jax.distributed); the mesh then
spans all hosts and the same script runs unchanged.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def parse_mesh(spec: str) -> dict:
    axes = {}
    for part in spec.split(","):
        name, _, size = part.partition("=")
        axes[name.strip()] = int(size)
    return axes


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--model", default="pythia-160m")
    p.add_argument("--mesh", default="fsdp=8", help='e.g. "fsdp=8" or "dp=2,fsdp=2,tp=2"')
    p.add_argument("--iters", type=int, default=10)
    p.add_argument("--global-batch-size", type=int, default=8)
    p.add_argument("--seq-len", type=int, default=None)
    p.add_argument("--optimizer", choices=("sgd", "adamw"), default="adamw")
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--weight-decay", type=float, default=0.1)
    p.add_argument("--seed", type=int, default=42)
    p.add_argument("--fixed-batch", action="store_true",
                   help="train on the first batch every step (the loss then falls)")
    return p.parse_args(argv)


def main(argv=None, *, config=None) -> dict:
    """Train and return what was measured. ``config`` (a ``GPTConfig``) takes
    the place of ``--model``: ``chip_smoke.py --rehearse`` cuts widths so."""
    args = parse_args(argv)

    import jax

    from thunder_tpu.core import dtypes
    from thunder_tpu.core.devices import device_description
    from thunder_tpu.models import gpt
    from thunder_tpu.parallel import (
        build_train_step,
        gpt_param_specs,
        make_mesh,
        named_shardings,
    )

    config = config or gpt.name_to_config(args.model)
    seq = args.seq_len or config.block_size
    mesh = make_mesh(**parse_mesh(args.mesh))
    print(f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))} model={args.model} "
          f"B={args.global_batch_size} T={seq}", file=sys.stderr)

    # Initialise straight into the sharding plan: every device generates its
    # own shards, and the whole model never sits on the first chip.
    specs = gpt_param_specs(config, mesh)
    params = jax.jit(
        lambda: gpt.init_params(config, dtype=dtypes.bfloat16, device_init=True, seed=args.seed),
        out_shardings=named_shardings(mesh, specs),
    )()

    rng = np.random.RandomState(args.seed)

    def draw():
        idx = rng.randint(0, config.vocab_size, (args.global_batch_size, seq)).astype(np.int32)
        return idx, np.roll(idx, -1, axis=1).astype(np.int32)

    first = draw()

    def batch():
        return first if args.fixed_batch else draw()

    idx, tgt = first
    t0 = time.perf_counter()
    step, opt_state, extrace = build_train_step(
        config, params, idx, tgt,
        mesh=mesh, param_specs=specs,
        lr=args.lr, weight_decay=args.weight_decay, optimizer=args.optimizer,
        return_extrace=True,
    )
    trace_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    params, opt_state, loss = step(params, opt_state, idx, tgt)
    losses = [float(np.asarray(loss))]
    compile_s = time.perf_counter() - t0
    print(f"trace+claim: {trace_s:.1f}s compile+first-step: {compile_s:.1f}s "
          f"loss={losses[0]:.4f}", file=sys.stderr)
    bytes_after_first = [(d.memory_stats() or {}).get("bytes_in_use") for d in mesh.devices.flat]

    t0 = time.perf_counter()
    for _ in range(args.iters):
        idx, tgt = batch()
        params, opt_state, loss = step(params, opt_state, idx, tgt)
        losses.append(loss)  # read after the timed window: no host sync in it
    loss.block_until_ready()
    total = time.perf_counter() - t0
    losses = [float(np.asarray(l)) for l in losses]
    for i, l in enumerate(losses[1:]):
        print(f"iter {i}: loss {l:.4f}", file=sys.stderr)

    tokens = args.global_batch_size * seq
    step_s = total / max(args.iters, 1)
    print(f"{args.iters} iters: {total:.2f}s  avg {step_s:.4f}s/iter  "
          f"{tokens * args.iters / total:,.0f} tok/s  {device_description()}")
    if not np.isfinite(losses[-1]):
        raise FloatingPointError(f"loss diverged: {losses}")
    return {
        "losses": losses, "step_s": step_s, "trace_s": trace_s, "compile_s": compile_s,
        "step": step, "extrace": extrace, "params": params, "opt_state": opt_state,
        "batch": (idx, tgt), "mesh": mesh, "specs": specs,
        "bytes_after_first": bytes_after_first,
    }


if __name__ == "__main__":
    main()
