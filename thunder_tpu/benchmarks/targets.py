"""Microbenchmark targets: op units × executor matrix, pytest-runnable.

Reference parity: thunder/benchmarks/targets.py (pytest-benchmark targets)
+ the executor-matrix benchmark constructions in benchmarks/__init__.py:699-976
(GeLU/softmax/cross-entropy/SDPA units and LitGPT block benchmarks run per
executor). Here each target compiles the op through the full jit pipeline
under a named executor list and reports the standard harness metrics.

Run as pytest (opt-in — benchmarks are not correctness CI):
    THUNDER_BENCH=1 pytest thunder_tpu/benchmarks/targets.py -q -s
or as a CLI:
    python -m thunder_tpu.benchmarks.targets [--filter sdpa] [--iters 20]
"""

from __future__ import annotations

import json
import os
import sys
from functools import partial

import numpy as np

try:  # the CLI path must work without test dependencies (ADVICE r3)
    import pytest
except ImportError:  # pragma: no cover
    class _PytestStub:
        class mark:
            @staticmethod
            def parametrize(*a, **k):
                return lambda fn: fn

        @staticmethod
        def skip(msg):
            raise RuntimeError(msg)

    pytest = _PytestStub()


def _enabled() -> bool:
    return bool(os.environ.get("THUNDER_BENCH"))


EXECUTOR_CONFIGS = {
    "jax": ["jax"],
    "kernels": ["flash", "pallas", "jax"],
    "quant": ["quant", "jax"],
}


def _rand(*shape, dtype=np.float32, seed=0):
    return (np.random.RandomState(seed + sum(shape)).randn(*shape) * 0.5).astype(dtype)


# -- unit definitions: name -> (fn builder over ltorch, example args) ---------


def _unit_gelu():
    import thunder_tpu.torch as ltorch

    x = _rand(4096, 4096)
    return lambda a: ltorch.gelu(a), (x,), 0


def _unit_softmax():
    import thunder_tpu.torch as ltorch

    x = _rand(256, 8192)
    return lambda a: ltorch.softmax(a, -1), (x,), 0


def _unit_layer_norm():
    import thunder_tpu.torch as ltorch

    x = _rand(4096, 4096)
    w, b = _rand(4096, seed=1), _rand(4096, seed=2)
    return lambda a, w, b: ltorch.layer_norm(a, (4096,), w, b), (x, w, b), 0


def _unit_cross_entropy():
    import thunder_tpu.torch as ltorch

    logits = _rand(4096, 32000)
    tgt = np.random.RandomState(3).randint(0, 32000, (4096,)).astype(np.int64)
    return lambda a, t: ltorch.cross_entropy(a, t), (logits, tgt), 0


def _unit_sdpa():
    import jax.numpy as jnp

    import thunder_tpu.torch as ltorch

    B, H, S, D = 4, 16, 2048, 128
    # bf16: the flash executor (like the reference's cudnn/sdpa seats)
    # claims half precision only.
    q, k, v = (jnp.asarray(_rand(B, H, S, D, seed=i), dtype=jnp.bfloat16) for i in range(3))
    flops = 4.0 * B * H * S * S * D  # 2 matmuls fwd
    return (
        lambda q, k, v: ltorch.scaled_dot_product_attention(q, k, v, is_causal=True),
        (q, k, v),
        flops,
    )


def _unit_linear():
    import thunder_tpu.torch as ltorch

    x, w = _rand(4096, 4096), _rand(4096, 4096, seed=1)
    return lambda a, w: ltorch.linear(a, w), (x, w), 2.0 * 4096**3


def _unit_gpt_block_fwd():
    from thunder_tpu.core import dtypes
    from thunder_tpu.models import gpt as m

    cfg = m.name_to_config("pythia-160m")
    params = m.init_params(cfg, dtype=dtypes.float32, seed=0)
    idx = np.random.RandomState(0).randint(0, cfg.vocab_size, (4, 512)).astype(np.int32)
    n = sum(int(np.prod(p.shape)) for p in _leaves(params))
    return lambda p, i: m.forward(p, i, cfg), (params, idx), 2.0 * n * 4 * 512


def _unit_rms_norm():
    import thunder_tpu.torch as ltorch

    x, w = _rand(8192, 4096), _rand(4096, seed=1)
    return lambda a, w: ltorch.rms_norm(a, (4096,), w), (x, w), 0


def _block_unit(cfg_name: str, *, train: bool, B: int = 1, T: int = 512):
    """One transformer BLOCK of a model family (reference:
    benchmarks/__init__.py LitGPT/nanoGPT block benchmarks at :699-976 —
    per-block fwd or fwd+bwd with the model's real geometry)."""
    from thunder_tpu.core import dtypes
    from thunder_tpu.models import gpt as m
    from thunder_tpu.models.gpt import _block, _rope_cache

    import thunder_tpu.torch as ltorch

    cfg = m.name_to_config(cfg_name)
    full = m.init_params(cfg, dtype=dtypes.bfloat16, seed=0)
    p = full["blocks"][0]
    x = _rand(B, T, cfg.n_embd).astype(np.float32)

    def block_fwd(x, p):
        import thunder_tpu.clang as clang

        xb = clang.maybe_convert_to_dtype(x, dtypes.bfloat16)
        cos, sin = _rope_cache(T, cfg, device=xb.device, dtype=xb.dtype)
        out = _block(xb, p, cos, sin, cfg)
        return ltorch.sum(clang.maybe_convert_to_dtype(out, dtypes.float32) ** 2)

    n = sum(int(np.prod(q.shape)) for q in _leaves(p))
    fwd_flops = 2.0 * n * B * T + 4.0 * B * cfg.n_head * T * T * cfg.head_size
    if not train:
        return block_fwd, (x, p), fwd_flops

    def block_train(x, p):
        return block_fwd(x, p)

    return block_train, (x, p), 3.0 * fwd_flops


def _unit_llama_block_fwd():
    return _block_unit("llama-2-7b", train=False)


def _unit_llama_block_train():
    fn, args, flops = _block_unit("llama-2-7b", train=True)
    fn._needs_grad = True  # run_target stages it via value_and_grad
    return fn, args, flops


def _unit_nanogpt_block_fwd():
    # pythia-160m's block IS the nanoGPT geometry class: parallel-residual
    # GPT block with LayerNorm + GELU MLP.
    return _block_unit("pythia-160m", train=False)


def _unit_nanogpt_block_train():
    fn, args, flops = _block_unit("pythia-160m", train=True)
    fn._needs_grad = True
    return fn, args, flops


def _leaves(tree):
    from thunder_tpu.core.pytree import tree_leaves

    return [p for p in tree_leaves(tree) if hasattr(p, "shape")]


UNITS = {
    "gelu": _unit_gelu,
    "softmax": _unit_softmax,
    "layer_norm": _unit_layer_norm,
    "rms_norm": _unit_rms_norm,
    "cross_entropy": _unit_cross_entropy,
    "sdpa": _unit_sdpa,
    "linear": _unit_linear,
    "gpt_block_fwd": _unit_gpt_block_fwd,
    "nanogpt_block_fwd": _unit_nanogpt_block_fwd,
    "nanogpt_block_train": _unit_nanogpt_block_train,
    "llama_block_fwd": _unit_llama_block_fwd,
    "llama_block_train": _unit_llama_block_train,
}


def run_target(unit: str, executor: str, *, iters: int = 10, warmup: int = 2) -> dict:
    import jax

    import thunder_tpu
    from thunder_tpu.benchmarks import run_benchmark
    from thunder_tpu.core.pytree import tree_map

    fn, args, flops = UNITS[unit]()
    # Device-resident inputs: a numpy arg would be uploaded again on every
    # iteration and swamp the op time.
    args = tree_map(
        lambda x: jax.device_put(x) if isinstance(x, np.ndarray) else x, args
    )
    if getattr(fn, "_needs_grad", False):
        jfn = thunder_tpu.value_and_grad(fn, executors=EXECUTOR_CONFIGS[executor])
    else:
        jfn = thunder_tpu.jit(fn, executors=EXECUTOR_CONFIGS[executor])
    result = run_benchmark(
        f"{unit}[{executor}]",
        partial(jfn, *args),
        warmup=warmup,
        iters=iters,
        flops_per_iter=flops or None,
        pipelined=True,
    )
    return result.summary()


# -- pytest targets (gated: benchmarks are not correctness CI) ----------------


@pytest.mark.parametrize("executor", list(EXECUTOR_CONFIGS))
@pytest.mark.parametrize("unit", list(UNITS))
def test_bench(unit, executor):
    if not _enabled():
        pytest.skip("set THUNDER_BENCH=1 to run benchmark targets")
    summary = run_target(unit, executor)
    print(json.dumps(summary))


def main() -> None:
    import argparse

    p = argparse.ArgumentParser()
    p.add_argument("--filter", default="")
    p.add_argument("--executors", default=",".join(EXECUTOR_CONFIGS))
    p.add_argument("--iters", type=int, default=10)
    p.add_argument("--format", choices=("jsonl", "table"), default="table",
                   help="table: per-unit × per-executor comparison matrix "
                        "(reference: the executor-comparison benchmark specs, "
                        "benchmarks/__init__.py:699-976)")
    args = p.parse_args()

    executors = [e for e in args.executors.split(",") if e]
    rows = []
    for unit in UNITS:
        if args.filter and args.filter not in unit:
            continue
        row = {"unit": unit}
        for executor in executors:
            try:
                summary = run_target(unit, executor, iters=args.iters)
            except Exception as e:  # noqa: BLE001 — report and continue the matrix
                summary = {"name": f"{unit}[{executor}]", "error": f"{type(e).__name__}: {e}"}
            if args.format == "jsonl":
                print(json.dumps(summary), flush=True)
            row[executor] = summary
        rows.append(row)

    if args.format != "table":
        return
    # comparison table: median time per executor + speedup vs the jax column
    headers = ["unit"] + [f"{e} (s)" for e in executors] + [
        f"{e} vs jax" for e in executors if e != "jax"
    ]
    print("  ".join(f"{h:>20s}" for h in headers))
    for row in rows:
        def med(e):
            s = row.get(e, {})
            return s.get("median_iter_time_s", s.get("average_iter_time_s"))

        cells = [f"{row['unit']:>20s}"]
        base = med("jax")
        for e in executors:
            m = med(e)
            cells.append(f"{m:20.5f}" if m is not None else f"{'ERR':>20s}")
        for e in executors:
            if e == "jax":
                continue
            m = med(e)
            cells.append(
                f"{base / m:19.2f}x" if (m and base) else f"{'-':>20s}"
            )
        print("  ".join(cells), flush=True)


if __name__ == "__main__":
    main()
