"""LitGPT-style end-to-end training benchmark CLI.

Reference parity: thunder/benchmarks/benchmark_litgpt.py:41 — model-name ×
batch × seq × distributed-config training benchmark reporting iteration
time, tokens/sec, TFLOP/s → MFU, and peak memory — plus the executor-matrix
comparison the reference publishes as its eager/inductor/thunder columns
(examples/lit-gpt/README.md): here the columns are executor stacks
(jax-only baseline → +flash → +pallas → +norm → +quant).

Usage:
    python -m thunder_tpu.benchmarks.litgpt --model pythia-160m \
        --micro-batch 4 --seq 1024 --iters 10 [--fsdp 8] [--tp 2] [--dp 2] \
        [--forward-only] [--dtype bfloat16]

    # executor-matrix comparison → markdown table (BENCHMARKS.md source):
    python -m thunder_tpu.benchmarks.litgpt --model pythia-410m --matrix \
        --micro-batch 4 --seq 2048 --iters 10 --markdown
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np

# Executor stacks for --matrix, ordered baseline → full. Names resolve via
# thunder_tpu.extend; "pallas,flash,jax" is the registered default list.
# norm and quant are opt-in executors.
MATRIX_STACKS: tuple[tuple[str, str], ...] = (
    ("jax", "jax"),
    ("+flash", "flash,jax"),
    ("+pallas (default)", "pallas,flash,jax"),
    ("+norm", "norm,pallas,flash,jax"),
    ("+quant int8", "quant,pallas,flash,jax"),
)


def parse_args(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="pythia-160m")
    p.add_argument("--micro-batch", type=int, default=4)
    p.add_argument("--seq", type=int, default=1024)
    p.add_argument("--iters", type=int, default=10)
    p.add_argument("--warmup", type=int, default=2)
    p.add_argument("--dp", type=int, default=1)
    p.add_argument("--fsdp", type=int, default=1)
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--forward-only", action="store_true")
    p.add_argument("--pipelined", action="store_true",
                   help="async-dispatch all iters, one final sync (no host "
                        "sync per iteration in the timed window)")
    p.add_argument("--optimizer", default="adamw", choices=("adamw", "sgd"))
    p.add_argument("--executors", default="",
                   help="comma list, e.g. quant,flash,pallas,jax (TE-seat "
                        "quantized-training evidence runs)")
    p.add_argument("--matrix", action="store_true",
                   help="run the executor-stack comparison matrix")
    p.add_argument("--markdown", action="store_true",
                   help="emit a markdown table (with --matrix)")
    return p.parse_args(argv)


def run_one(args, executors=None):
    """One benchmark configuration → summary dict."""
    from thunder_tpu.benchmarks import (
        count_params,
        forward_flops_per_token,
        run_benchmark,
        training_flops_per_token,
    )
    from thunder_tpu.core import dtypes
    from thunder_tpu.models import gpt as m

    cfg = m.name_to_config(args.model)
    seq = min(args.seq, cfg.block_size)
    params = m.init_params(cfg, dtype=dtypes.to_dtype(args.dtype), device_init=True, seed=0)
    n_params = count_params(params)

    rng = np.random.RandomState(0)
    idx = rng.randint(0, cfg.vocab_size, (args.micro_batch, seq)).astype(np.int32)
    tgt = np.roll(idx, -1, axis=1).astype(np.int32)
    tokens = args.micro_batch * seq

    mesh = None
    if args.dp * args.fsdp * args.tp > 1:
        from thunder_tpu.parallel import make_mesh
        from thunder_tpu.parallel.sharding import gpt_param_specs, shard_pytree

        mesh = make_mesh(dp=args.dp, fsdp=args.fsdp, tp=args.tp)
        specs = gpt_param_specs(cfg, mesh)
        params = shard_pytree(params, mesh, specs)

    ex_list = [e for e in (executors or "").split(",") if e] or None

    if args.forward_only:
        import jax

        from thunder_tpu.api import trace_program
        from thunder_tpu.core.pytree import tree_flatten
        from thunder_tpu.executors.passes import transform_for_execution
        from thunder_tpu.extend import resolve_executors
        from thunder_tpu.transforms.common import dce

        fn = lambda p, i: m.forward(p, i, cfg)  # noqa: E731
        _, comp = trace_program(fn, (params, idx), {})
        ex = transform_for_execution(dce(comp), resolve_executors(ex_list))
        jfn = jax.jit(ex.python_callable())
        flat, _ = tree_flatten(((params, idx), {}))
        result = run_benchmark(
            f"{args.model}-fwd", lambda: jfn(*flat), warmup=args.warmup, iters=args.iters,
            tokens_per_iter=tokens, flops_per_iter=forward_flops_per_token(n_params) * tokens,
            pipelined=args.pipelined,
        )
        losses = None
    else:
        from thunder_tpu.parallel import build_train_step
        from thunder_tpu.parallel.sharding import gpt_param_specs

        specs = gpt_param_specs(cfg, mesh) if mesh is not None else None
        step, opt = build_train_step(
            cfg, params, idx, tgt, mesh=mesh, param_specs=specs, lr=args.lr,
            donate=(args.optimizer == "sgd"), grads_in_f32=(args.optimizer != "sgd"),
            executors=ex_list, optimizer=args.optimizer,
        )
        state = {"params": params, "opt": opt}
        losses = []

        def one_step():
            state["params"], state["opt"], loss = step(state["params"], state["opt"], idx, tgt)
            losses.append(loss)
            return loss

        result = run_benchmark(
            f"{args.model}-train", one_step, warmup=args.warmup, iters=args.iters,
            tokens_per_iter=tokens, flops_per_iter=training_flops_per_token(n_params) * tokens,
            pipelined=args.pipelined,
        )

    summary = result.summary()
    if losses is not None:
        summary["loss_first"] = round(float(np.asarray(losses[0])), 4)
        summary["loss_last"] = round(float(np.asarray(losses[-1])), 4)
    if executors:
        summary["executors"] = executors
    summary["n_params"] = n_params
    summary["mesh"] = {"dp": args.dp, "fsdp": args.fsdp, "tp": args.tp}
    return summary


def _matrix_markdown(args, rows) -> str:
    from thunder_tpu.benchmarks import device_description

    mode = "fwd" if args.forward_only else "train"
    lines = [
        f"### {args.model} {mode} — B={args.micro_batch} T={args.seq} "
        f"dtype={args.dtype} iters={args.iters} ({device_description()})",
        "",
        "| executors | avg iter (s) | median (s) | tokens/s | TFLOP/s | MFU | mem (GB) | loss (first→last) |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for label, s in rows:
        loss = (f"{s['loss_first']}→{s['loss_last']}" if "loss_first" in s else "—")
        lines.append(
            f"| {label} | {s.get('average_iter_time_s', '—')} "
            f"| {s.get('median_iter_time_s', '—')} "
            f"| {s.get('tokens_per_sec', '—')} | {s.get('model_tflop_per_sec', '—')} "
            f"| {s.get('mfu') or '—'} | {s.get('memory_used_GB', '—')} | {loss} |"
        )
    return "\n".join(lines)


def main(argv=None) -> None:
    args = parse_args(argv)

    if not args.matrix:
        print(json.dumps(run_one(args, args.executors or None)))
        return

    rows = []
    for label, stack in MATRIX_STACKS:
        try:
            summary = run_one(args, stack)
        except Exception as e:  # a stack that can't run here (e.g. quant on CPU)
            print(f"# {label}: skipped ({type(e).__name__}: {e})", file=sys.stderr)
            continue
        rows.append((label, summary))
        print(f"# {label}: {json.dumps(summary)}", file=sys.stderr)

    if args.markdown:
        print(_matrix_markdown(args, rows))
    else:
        print(json.dumps({label: s for label, s in rows}))


if __name__ == "__main__":
    main()
