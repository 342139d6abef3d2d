#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that HEAD still starts on the chip.

    python chip_smoke.py              # the chip run; anything but a TPU exits non-zero
    python chip_smoke.py --rehearse   # same stages, tiny widths, 8 virtual CPU devices

One process, no children: a chip belongs to one process at a time. The stages
drive the normal entry points (``examples/train.py`` and
``examples/train_fsdp.py``'s ``main``, ``parallel.build_train_step``,
``thunder_tpu.jit``) at the full width of pythia-410m and at the widths of
mistral-7b, weights random from a seed:

  A  trainer, one chip: pythia-410m, all 24 layers, AdamW, B=2, T=2048
  B  the kernels pythia does not reach (rope, grouped-query attention):
     mistral-7b cut to 2 layers, against the same step on the ``jax`` executor
  C  the dispatcher: ``thunder_tpu.jit`` forward of pythia-410m, B=4, T=2048
  D  the compile cache: where it is, and what it hit
  E  four chips (when jax reports four): ``fsdp=4``, global batch 8, against a
     one-chip run of the same batch and seed

A stage passes only if what came out is right: finite, falling losses that
agree with a reference; kernels claimed by ``flash``/``pallas`` and compiled
by Mosaic (``tpu_custom_call`` in the compiled text), not interpreted; nothing
demoted, de-optimized or quarantined on the way. The last line of stdout is the
result, ``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
with the device as jax reports it and no other key; the line before it,
``summary: {...}``, holds each stage's ``ok``, seconds and observations. The
exit code is 0 only if every stage that ran passed. Every time printed is an
observation with the device named beside it, not a metric.

``--rehearse`` is for the tests and for debugging before a chip call. It is an
explicit argument and never a default, and its summary says ``"chip": false``.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.metadata
import importlib.util
import json
import os
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))

# Stated tolerances. bf16 keeps 8 bits of mantissa, so one rounding is worth
# 2**-8 = 3.9e-3 of a value; the losses compared are float32 means over
# thousands of rows of a float32 log-softmax. Differences are taken against
# the first-step loss, the scale of the quantity: on a fixed batch a wide
# model's loss is near zero after one AdamW step (0.0145 against 10.4 on the
# v5e, 2026-09-26), where a difference relative to itself is all noise.
# KERNEL_VS_JAX: the same step with Pallas kernels and with plain XLA ops
# differs by bf16 roundings inside attention and rope. First step: the weights
# are identical, and half a bf16 rounding of the loss is allowed. Later steps
# also carry the difference through AdamW, whose first updates are +-lr
# whatever the gradient's size: two roundings are allowed.
KERNEL_VS_JAX_RTOL_FIRST = 2e-3
KERNEL_VS_JAX_RTOL_LATER = 8e-3
# SHARDED_VS_ONE_CHIP: fsdp gathers whole weights, so each chip computes its
# rows exactly as one chip would; only the order of the float32 sum differs.
SHARDED_VS_ONE_CHIP_RTOL = 1e-3
BALANCE = 0.25  # bytes_in_use on devices 1..3 against device 0
MAX_STEP_S = 1.0  # pythia-410m B=2 T=2048 took 0.12 s/iter on a v5e on 2026-07-30

KERNEL_EXECUTORS = ("flash", "pallas")


class SmokeFailure(AssertionError):
    """A check of the smoke did not hold."""


def require(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


# -----------------------------------------------------------------------------
# Sizes: the chip run and its rehearsal differ in these and in nothing else
# -----------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Sizes:
    chip: bool
    train_model: str  # stages A, C, E
    gqa_model: str  # stage B
    vocab: int | None  # rehearsal only: a vocabulary the cross-entropy kernel claims
    batch: int
    seq: int
    fwd_batch: int
    global_batch: int


CHIP = Sizes(chip=True, train_model="pythia-410m", gqa_model="mistral-7b", vocab=None,
             batch=2, seq=2048, fwd_batch=4, global_batch=8)
REHEARSAL = Sizes(chip=False, train_model="gpt-tiny", gqa_model="llama-tiny", vocab=128,
                  batch=2, seq=128, fwd_batch=4, global_batch=8)
GQA_LAYERS = 2  # stage B cuts depth, never width


def config_for(sizes: Sizes, name: str, **replace):
    from thunder_tpu.models import gpt

    cfg = gpt.name_to_config(name)
    if sizes.vocab is not None:
        replace.update(vocab_size=sizes.vocab, padded_vocab_size=sizes.vocab)
    return dataclasses.replace(cfg, **replace) if replace else cfg


def load_example(name: str):
    """``examples/<name>.py`` as a module: the smoke calls the function the
    example runs, it does not copy it."""
    spec = importlib.util.spec_from_file_location(f"example_{name}",
                                                  os.path.join(HERE, "examples", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -----------------------------------------------------------------------------
# Checks shared by the stages
# -----------------------------------------------------------------------------


def kernel_claims(trace) -> dict:
    """{symbol name: executor name} for what ``flash``/``pallas`` claimed."""
    out = {}
    for bsym in trace.bound_symbols:
        ex = bsym.sym.executor
        if ex is not None and ex.name in KERNEL_EXECUTORS:
            out[bsym.sym.name] = ex.name
    return out


def require_claims(trace, names) -> dict:
    claims = kernel_claims(trace)
    missing = [n for n in names if n not in claims]
    require(not missing, f"not claimed by {KERNEL_EXECUTORS}: {missing}; claimed: {claims}")
    return claims


def compiled_text(jitted, *args) -> str:
    return jitted.lower(*args).compile().as_text()


def require_mosaic(sizes: Sizes, text: str) -> int:
    """Kernels compiled by Mosaic, not interpreted. On the CPU rehearsal the
    kernels run in interpret mode and there is nothing to count."""
    n = text.count("tpu_custom_call")
    if sizes.chip:
        require(n > 0, "no tpu_custom_call in the compiled step: the kernels are not Mosaic calls")
    return n


def require_on_device(sizes: Sizes, *arrays) -> None:
    want = "tpu" if sizes.chip else "cpu"
    for a in arrays:
        platforms = {d.platform for d in a.devices()}
        require(platforms == {want}, f"output lives on {platforms}, not on {want}")


def require_trained_fast(sizes: Sizes, run: dict) -> None:
    """One trace of the step for the whole run, and on the chip a step under
    ``MAX_STEP_S``. A second trace is a second XLA compile (a minute at this
    size) hidden in the loop: PR 21 met one under every mesh, because the
    optimizer state went in laid out otherwise than it came back."""
    traces = run["step"]._cache_size()
    require(traces == 1, f"the step was traced {traces} times, not once")
    if sizes.chip:
        require(run["step_s"] < MAX_STEP_S,
                f"a step took {run['step_s']:.3f} s: something is not on the chip")


def require_falling(losses) -> None:
    import math

    require(all(math.isfinite(l) for l in losses), f"non-finite loss: {losses}")
    require(losses[-1] < losses[0], f"loss did not fall: {losses}")


def require_close(a: float, b: float, rtol: float, what: str, scale: float | None = None) -> float:
    """|a - b| within ``rtol`` of ``scale`` (default: the larger of the two)."""
    rel = abs(a - b) / (scale if scale is not None else max(abs(a), abs(b)))
    require(rel <= rtol, f"{what}: {a:.6f} vs {b:.6f}, difference {rel:.2e} of the scale > {rtol:.0e}")
    return rel


def require_nothing_hidden() -> None:
    """No demotion and no de-opt happened anywhere in the process so far."""
    from thunder_tpu.resilience import demotion, deopt

    require(demotion.quarantine_snapshot() == {},
            f"executors were demoted: {demotion.quarantine_snapshot()}")
    require(deopt.process_max_level() == 0,
            f"a function was de-optimized to level {deopt.process_max_level()}")


def cache_counts() -> dict:
    from thunder_tpu import api

    c = api._jax_cache_counts()
    return {"hits": c["hits"], "misses": c["misses"]}


def cache_delta(before: dict) -> dict:
    after = cache_counts()
    return {k: after[k] - before[k] for k in after}


def memory_observation() -> dict:
    import jax

    d = jax.devices()[0]
    stats = d.memory_stats() or {}
    return {"device": str(d), "peak_bytes_in_use": stats.get("peak_bytes_in_use")}


def leaves(tree):
    import jax

    return jax.tree_util.tree_leaves(tree)


# -----------------------------------------------------------------------------
# Stages
# -----------------------------------------------------------------------------


def stage_a_trainer(sizes: Sizes) -> dict:
    """The function ``examples/train.py`` runs, at full width and depth."""
    before = cache_counts()
    run = load_example("train").main(
        ["--model", sizes.train_model, "--optimizer", "adamw", "--lr", "3e-4",
         "--micro-batch-size", str(sizes.batch), "--seq-len", str(sizes.seq),
         "--warmup", "2", "--iters", "5", "--fixed-batch"],
        config=config_for(sizes, sizes.train_model) if not sizes.chip else None,
    )
    step_cache = cache_delta(before)
    require_falling(run["losses"])
    claims = require_claims(run["extrace"],
                            ["sdpa_fwd_res", "sdpa_bwd_res", "cross_entropy", "cross_entropy_bwd"])
    require_on_device(sizes, *leaves(run["params"])[:2], *leaves(run["opt_state"])[:2])
    text = compiled_text(run["step"], run["params"], run["opt_state"], *run["batch"])
    n_mosaic = require_mosaic(sizes, text)
    require_trained_fast(sizes, run)
    require_nothing_hidden()
    return {
        "model": sizes.train_model, "batch": sizes.batch, "seq": sizes.seq,
        "losses": [round(l, 4) for l in run["losses"]],
        "step_s": round(run["step_s"], 4), "trace_claim_s": round(run["trace_s"], 2),
        "compile_first_step_s": round(run["compile_s"], 2),
        "step_persistent_cache": step_cache,
        "claims": claims, "tpu_custom_calls": n_mosaic, **memory_observation(),
    }


def stage_b_gqa_rope(sizes: Sizes) -> dict:
    """Rope and grouped-query attention through ``build_train_step``, and the
    same step on the ``jax`` executor as the reference."""
    import numpy as np

    from thunder_tpu.core import dtypes
    from thunder_tpu.models import gpt
    from thunder_tpu.parallel import build_train_step

    cfg = config_for(sizes, sizes.gqa_model, n_layer=GQA_LAYERS)
    require(cfg.query_groups != cfg.n_head and cfg.rotary_percentage == 1.0,
            f"{cfg.name} does not exercise grouped-query attention and full rope")
    rng = np.random.RandomState(0)
    idx = rng.randint(0, cfg.vocab_size, (sizes.batch, sizes.seq)).astype(np.int32)
    tgt = np.roll(idx, -1, axis=1).astype(np.int32)

    def train(executors):
        params = gpt.init_params(cfg, dtype=dtypes.bfloat16, device_init=True, seed=0)
        t0 = time.perf_counter()
        step, opt, extrace = build_train_step(cfg, params, idx, tgt, optimizer="adamw",
                                              executors=executors, return_extrace=True)
        losses = []
        for _ in range(3):
            params, opt, loss = step(params, opt, idx, tgt)
            losses.append(loss)
        loss.block_until_ready()
        seconds = time.perf_counter() - t0
        require_on_device(sizes, loss, *leaves(params)[:2])
        text = compiled_text(step, params, opt, idx, tgt)
        return [float(np.asarray(l)) for l in losses], extrace, text, seconds

    k_losses, k_trace, k_text, k_s = train(None)
    require_falling(k_losses)
    claims = require_claims(k_trace, ["apply_rope", "sdpa_fwd_res", "sdpa_bwd_res",
                                      "cross_entropy", "cross_entropy_bwd"])
    n_mosaic = require_mosaic(sizes, k_text)
    del k_trace, k_text
    gc.collect()

    j_losses, j_trace, j_text, j_s = train(["jax"])
    require(kernel_claims(j_trace) == {}, "the reference step claimed a kernel")
    require("tpu_custom_call" not in j_text, "the reference step holds a Mosaic call")
    log(f"losses with kernels {k_losses}, on the jax executor {j_losses}")
    rel = [require_close(k, j, KERNEL_VS_JAX_RTOL_FIRST if i == 0 else KERNEL_VS_JAX_RTOL_LATER,
                         f"step {i} loss, kernels vs jax executor", scale=j_losses[0])
           for i, (k, j) in enumerate(zip(k_losses, j_losses))]
    require_nothing_hidden()
    return {
        "model": f"{cfg.name} x{cfg.n_layer} layers", "heads": [cfg.n_head, cfg.query_groups],
        "batch": sizes.batch, "seq": sizes.seq,
        "losses_kernels": [round(l, 4) for l in k_losses],
        "losses_jax": [round(l, 4) for l in j_losses],
        "difference_over_first_loss": [float(f"{r:.2e}") for r in rel],
        "rtol": [KERNEL_VS_JAX_RTOL_FIRST, KERNEL_VS_JAX_RTOL_LATER],
        "build_and_3_steps_s": {"kernels": round(k_s, 2), "jax": round(j_s, 2)},
        "claims": claims, "tpu_custom_calls": n_mosaic, **memory_observation(),
    }


def stage_c_dispatcher(sizes: Sizes) -> dict:
    """``thunder_tpu.jit``: one miss, one hit, nothing demoted on the way."""
    import numpy as np

    import thunder_tpu
    from thunder_tpu.core import dtypes
    from thunder_tpu.models import gpt

    cfg = config_for(sizes, sizes.train_model)
    params = gpt.init_params(cfg, dtype=dtypes.bfloat16, device_init=True, seed=0)
    idx = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (sizes.fwd_batch, sizes.seq)).astype(np.int32)
    jfn = thunder_tpu.jit(lambda p, i: gpt.forward(p, i, cfg))

    t0 = time.perf_counter()
    first = jfn(params, idx)
    first.block_until_ready()
    miss_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    second = jfn(params, idx)
    second.block_until_ready()
    hit_s = time.perf_counter() - t0

    require((thunder_tpu.cache_misses(jfn), thunder_tpu.cache_hits(jfn)) == (1, 1),
            f"expected one miss then one hit, got misses={thunder_tpu.cache_misses(jfn)} "
            f"hits={thunder_tpu.cache_hits(jfn)}")
    info = thunder_tpu.cache_info(jfn)
    require(info["degradation_level"] == 0 and all(
        e["degradation_level"] == 0 for e in info["entries"]), f"de-optimized: {info}")
    require_nothing_hidden()
    claims = require_claims(thunder_tpu.last_traces(jfn)[-1], ["scaled_dot_product_attention"])
    require(first.shape == (sizes.fwd_batch, sizes.seq, cfg.padded_vocab_size),
            f"logits of shape {first.shape}")
    require_on_device(sizes, first)
    require(bool(np.isfinite(np.asarray(first[:, -1, :], dtype=np.float32)).all()),
            "non-finite logits")
    require(bool((np.asarray(first[:, -1, :]) == np.asarray(second[:, -1, :])).all()),
            "the hit did not reproduce the miss")
    entry = thunder_tpu.compile_stats(jfn).cache_entries[-1]
    n_mosaic = require_mosaic(
        sizes, compiled_text(entry.computation_fn, *entry.hlo_audit_avals))
    phases = {k: (round(v, 3) if isinstance(v, float) else v)
              for k, v in entry.stats.phases.items()}
    require("hlo_audit" in phases, f"no hlo_audit among the compile phases: {sorted(phases)}")
    return {
        "model": sizes.train_model, "batch": sizes.fwd_batch, "seq": sizes.seq,
        "miss_s": round(miss_s, 2), "hit_s": round(hit_s, 4),
        "compile_phases_s": phases,
        "claims": claims, "tpu_custom_calls": n_mosaic, **memory_observation(),
    }


def stage_d_compile_cache(sizes: Sizes) -> dict:
    """The cache is where it was placed from outside, or in the checkout."""
    import jax

    from thunder_tpu import api

    want = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(HERE, ".jax_cache")
    have = jax.config.jax_compilation_cache_dir
    require(have is not None and os.path.realpath(have) == os.path.realpath(want),
            f"compile cache at {have!r}, expected {want!r}")
    counts = api._jax_cache_counts()
    require(counts["hits"] + counts["misses"] > 0, "the persistent cache saw no compile")
    return {
        "dir": have, "from_env": bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
        "entries": sum(1 for f in os.listdir(have) if os.path.isfile(os.path.join(have, f))),
        "hits": counts["hits"], "misses": counts["misses"],
        "backend_compile_s": round(counts["backend_compile_s"], 2),
        "cache_get_s": round(counts["cache_get_s"], 2),
    }


def stage_e_four_chips(sizes: Sizes) -> dict:
    """The function ``examples/train_fsdp.py`` runs, and the same global batch
    and seed on one chip."""
    import jax
    import numpy as np

    from thunder_tpu.core import dtypes
    from thunder_tpu.models import gpt
    from thunder_tpu.parallel import adamw_init, build_train_step

    seed, lr, wd = 42, 3e-4, 0.1
    cfg = config_for(sizes, sizes.train_model)
    devices = jax.devices()[:4]
    baseline = [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]

    run = load_example("train_fsdp").main(
        ["--mesh", "fsdp=4", "--model", sizes.train_model, "--optimizer", "adamw",
         "--lr", str(lr), "--weight-decay", str(wd), "--seed", str(seed),
         "--global-batch-size", str(sizes.global_batch), "--seq-len", str(sizes.seq),
         "--iters", "5", "--fixed-batch"],
        config=cfg if not sizes.chip else None,
    )
    require_falling(run["losses"])
    require_trained_fast(sizes, run)
    claims = require_claims(run["extrace"],
                            ["sdpa_fwd_res", "sdpa_bwd_res", "cross_entropy", "cross_entropy_bwd"])
    idx, tgt = run["batch"]
    text = compiled_text(run["step"], run["params"], run["opt_state"], idx, tgt)
    n_mosaic = require_mosaic(sizes, text)
    collectives = {op: text.count(f" {op}(") + text.count(f" {op}-start(")
                   for op in ("all-gather", "reduce-scatter", "all-reduce")}
    require(collectives["all-gather"] > 0
            and collectives["reduce-scatter"] + collectives["all-reduce"] > 0,
            f"the sharded step holds no gather/reduce collectives: {collectives}")

    from jax.sharding import PartitionSpec

    flat_specs = jax.tree_util.tree_leaves(
        run["specs"], is_leaf=lambda s: isinstance(s, PartitionSpec))
    sharded = 0
    for tree in (run["params"], run["opt_state"]["m"], run["opt_state"]["v"]):
        for leaf, spec in zip(leaves(tree), flat_specs):
            if any(axis is not None for axis in spec):
                sharded += 1
                require(len(leaf.sharding.device_set) == 4 and not leaf.sharding.is_fully_replicated,
                        f"a leaf with spec {spec} sits on {len(leaf.sharding.device_set)} "
                        f"device(s): {leaf.sharding}")
    require(sharded > 0, "no parameter has a sharded spec")
    require_on_device(sizes, *leaves(run["params"])[:2])

    used = None
    if all(b is not None for b in run["bytes_after_first"]) and all(b is not None for b in baseline):
        used = [after - before for after, before in zip(run["bytes_after_first"], baseline)]
        require(all(abs(u - used[0]) <= BALANCE * used[0] for u in used[1:]),
                f"memory is not spread over the four devices: {used} bytes in use by this stage")
    else:
        require(not sizes.chip, "the TPU backend reported no memory_stats")

    sharded_first = run["losses"][0]
    fsdp_obs = {k: run[k] for k in ("step_s", "trace_s", "compile_s")}
    del run, text
    gc.collect()

    # One chip, same seed, same global batch: in micro-batches, since the whole
    # batch with its activations does not fit one chip's memory. The first
    # step's loss is taken before any update, so the mean over micro-batches
    # of equal size is the global batch's loss.
    micro = sizes.batch
    require(sizes.global_batch % micro == 0, "global batch does not split into micro-batches")

    def fresh():
        return gpt.init_params(cfg, dtype=dtypes.bfloat16, device_init=True, seed=seed)

    params = fresh()
    step, opt = build_train_step(cfg, params, idx[:micro], tgt[:micro],
                                 lr=lr, weight_decay=wd, optimizer="adamw")
    parts = []
    for lo in range(0, sizes.global_batch, micro):
        if lo:  # the step donates what it is given
            params = fresh()
            opt = adamw_init(params)
        params, opt, loss = step(params, opt, idx[lo:lo + micro], tgt[lo:lo + micro])
        parts.append(float(np.asarray(loss)))
    one_chip_first = float(np.mean(parts))
    rel = require_close(sharded_first, one_chip_first, SHARDED_VS_ONE_CHIP_RTOL,
                        "first-step loss, fsdp=4 vs one chip")
    require_nothing_hidden()
    return {
        "model": sizes.train_model, "mesh": "fsdp=4", "global_batch": sizes.global_batch,
        "seq": sizes.seq, "first_loss_fsdp4": round(sharded_first, 5),
        "first_loss_one_chip": round(one_chip_first, 5),
        "relative_difference": float(f"{rel:.2e}"), "rtol": SHARDED_VS_ONE_CHIP_RTOL,
        "step_s": round(fsdp_obs["step_s"], 4), "trace_claim_s": round(fsdp_obs["trace_s"], 2),
        "compile_first_step_s": round(fsdp_obs["compile_s"], 2),
        "collectives": collectives, "sharded_leaves": sharded,
        "stage_bytes_in_use_per_device": used,
        "claims": claims, "tpu_custom_calls": n_mosaic, **memory_observation(),
    }


STAGES = (
    ("A_trainer", stage_a_trainer),
    ("B_gqa_rope", stage_b_gqa_rope),
    ("C_dispatcher", stage_c_dispatcher),
    ("D_compile_cache", stage_d_compile_cache),
    ("E_four_chips", stage_e_four_chips),
)


# -----------------------------------------------------------------------------
# Driver
# -----------------------------------------------------------------------------


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--rehearse", action="store_true",
                        help="tiny widths on 8 virtual CPU devices; never proof of the chip")
    args = parser.parse_args(argv)
    sizes = REHEARSAL if args.rehearse else CHIP

    if args.rehearse:
        # Before jax is imported. The kernels then run in Pallas interpret mode.
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_device_count=8").strip()
        os.environ["THUNDER_FLASH_FORCE"] = "1"

    import jax
    import jaxlib

    # The device gate. Nothing of the program has been imported yet.
    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = None
    log(f"device: {device} jax={jax.__version__} jaxlib={jaxlib.__version__} libtpu={libtpu}")
    want = "tpu" if sizes.chip else "cpu"
    if device["platform"] != want:
        print(f"chip_smoke: jax reports platform {device['platform']!r}, this run needs "
              f"{want!r}; nothing was run", file=sys.stderr)
        return 2

    # The program itself. Where it is absent (this file alone in a directory)
    # the import fails here, outside any stage: a non-zero exit and no result.
    sys.path.insert(0, HERE)
    import thunder_tpu  # noqa: F401

    summary = {"ok": True, "device": device, "chip": sizes.chip,
               "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__, "libtpu": libtpu},
               "stages": {}}
    for name, stage in STAGES:
        if stage is stage_e_four_chips and len(devices) < 4:
            # A fact about the machine, and the only stage allowed not to run.
            summary["multichip"] = f"not run ({len(devices)} device)"
            log(f"== {name}: {summary['multichip']}")
            continue
        log(f"== {name}")
        t0 = time.perf_counter()
        try:
            record = {"ok": True, **stage(sizes)}
        except Exception as e:  # the boundary: the failure is reported, and fails the run
            traceback.print_exc()
            record = {"ok": False, "error": f"{type(e).__name__}: {e}"[:2000]}
            summary["ok"] = False
        record["seconds"] = round(time.perf_counter() - t0, 2)
        summary["stages"][name] = record
        log(f"== {name}: {'ok' if record['ok'] else 'FAILED'} {json.dumps(record)}")
        # Free the stage's arrays and executables before the next one.
        gc.collect()
        jax.clear_caches()
    # The stages' observations, then the result: the last line of stdout holds
    # "ok" and "device" and nothing else, which is what the chip check reads.
    log(f"summary: {json.dumps(summary)}")
    log(json.dumps({"ok": summary["ok"], "device": device}))
    return 0 if summary["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
