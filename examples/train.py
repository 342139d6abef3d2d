"""Single-device pretraining example (reference: examples/lit-gpt/train.py).

The reference's headline workload — litgpt-style model, bf16-true,
SGD(lr=6e-4, wd=0.1), synthetic batches, static shapes — built the
thunder_tpu way: the whole step (forward + backward + optimizer) traces
through the framework and stages as ONE donated-buffer XLA executable.

Run (on the TPU; on a CPU it is a functional check, and the result line
names the device either way):
    python examples/train.py                           # pythia-160m, 20 iters
    python examples/train.py --model open_llama_3b     # the reference config
    python examples/train.py --optimizer adamw --lr 3e-4

``main`` returns what it measured, and ``chip_smoke.py`` calls it: the smoke
drives this path, not a copy of it.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--model", default="pythia-160m", help="config name (models/gpt.py registry)")
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--micro-batch-size", type=int, default=2)
    p.add_argument("--seq-len", type=int, default=None, help="default: the model's block_size")
    p.add_argument("--optimizer", choices=("sgd", "adamw"), default="sgd")
    p.add_argument("--lr", type=float, default=6e-4)
    p.add_argument("--weight-decay", type=float, default=0.1)
    p.add_argument("--seed", type=int, default=42)
    p.add_argument("--fixed-batch", action="store_true",
                   help="train on the first batch every step (the loss then falls)")
    return p.parse_args(argv)


def synthetic_batch(rng: np.random.RandomState, vocab: int, batch: int, seq: int):
    """The reference trains on a DummyDataset of random token ids; next-token
    targets are the inputs shifted by one."""
    idx = rng.randint(0, vocab, (batch, seq)).astype(np.int32)
    tgt = np.roll(idx, -1, axis=1).astype(np.int32)
    return idx, tgt


def main(argv=None, *, config=None) -> dict:
    """Train and return what was measured. ``config`` (a ``GPTConfig``) takes
    the place of ``--model``: ``chip_smoke.py --rehearse`` cuts widths so."""
    args = parse_args(argv)

    from thunder_tpu.core import dtypes
    from thunder_tpu.core.devices import device_description
    from thunder_tpu.models import gpt
    from thunder_tpu.parallel import build_train_step

    config = config or gpt.name_to_config(args.model)
    seq = args.seq_len or config.block_size
    print(f"model={args.model} layers={config.n_layer} d={config.n_embd} "
          f"B={args.micro_batch_size} T={seq} opt={args.optimizer}", file=sys.stderr)

    t0 = time.perf_counter()
    params = gpt.init_params(config, dtype=dtypes.bfloat16, device_init=True, seed=args.seed)
    print(f"init: {time.perf_counter() - t0:.1f}s", file=sys.stderr)

    rng = np.random.RandomState(args.seed)
    first = synthetic_batch(rng, config.vocab_size, args.micro_batch_size, seq)

    def batch():
        if args.fixed_batch:
            return first
        return synthetic_batch(rng, config.vocab_size, args.micro_batch_size, seq)

    idx, tgt = first
    t0 = time.perf_counter()
    step, opt_state, extrace = build_train_step(
        config, params, idx, tgt,
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

    for _ in range(args.warmup):
        idx, tgt = batch()
        params, opt_state, loss = step(params, opt_state, idx, tgt)
        losses.append(loss)
    loss.block_until_ready()

    tokens = args.micro_batch_size * seq
    t0 = time.perf_counter()
    for _ in range(args.iters):
        idx, tgt = batch()
        params, opt_state, loss = step(params, opt_state, idx, tgt)
        losses.append(loss)  # read after the timed window: no host sync in it
    loss.block_until_ready()
    total = time.perf_counter() - t0
    losses = [float(np.asarray(l)) for l in losses]
    for i, l in enumerate(losses[1 + args.warmup:]):
        print(f"iter {i}: loss {l:.4f}", file=sys.stderr)

    step_s = total / max(args.iters, 1)
    print(f"{args.iters} iters: {total:.2f}s  avg {step_s:.4f}s/iter  "
          f"{tokens * args.iters / total:,.0f} tok/s  {device_description()}")
    if not np.isfinite(losses[-1]):
        raise FloatingPointError(f"loss diverged: {losses}")
    return {
        "losses": losses, "step_s": step_s, "trace_s": trace_s, "compile_s": compile_s,
        "step": step, "extrace": extrace, "params": params, "opt_state": opt_state,
        "batch": (idx, tgt),
    }


if __name__ == "__main__":
    main()
