"""Benchmark entry point: one JSON line for the driver.

Primary workload (the north-star half): the reference's single-device
TRAINING benchmark — open_llama_3b, bf16-true, SGD(wd=0.1, no momentum),
micro-batch 2 × T=2048, 45 timed iters (reference: examples/lit-gpt/train.py,
thunder on A100-40GB: 21.9 s / 45 iters = 0.4867 s/iter — BASELINE.md).
The full step (fw + bw + SGD update) stages as ONE XLA executable with
donated params; min-cut rematerialization bounds saved activations.

Also reported: the forward-only headline (open_llama_3b fwd B=10×T=2048,
reference thunder: 1.27 s).

vs_baseline = reference_thunder_time / our_time (>1 ⇒ faster than the
reference's thunder+nvFuser on A100).
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np

REF_TRAIN_ITER_A100_S = 21.9 / 45  # examples/lit-gpt/README.md:35-39
REF_FWD_A100_S = 1.27  # examples/lit-gpt/README.md:18-22
TRAIN_B, TRAIN_T = 2, 2048  # reference train.py: micro_batch_size=2
FWD_B, FWD_T = 10, 2048
N_PARAMS = 3.43e9  # open_llama_3b
LR, WD = 6e-4, 0.1  # reference train.py


def _trace_claim(fn, args):
    from thunder_tpu.api import trace_program
    from thunder_tpu.transforms.common import cse, dce

    _, comp = trace_program(fn, args, {})
    return cse(dce(comp))


def _executors():
    """Executor list for the bench (THUNDER_BENCH_EXECUTORS="norm,flash,..."
    overrides; default = the registered default list). Used for A/B runs of
    opt-in executors (norm, quant) against the default stack."""
    import os

    from thunder_tpu.extend import resolve_executors

    spec = os.environ.get("THUNDER_BENCH_EXECUTORS")
    if not spec:
        return resolve_executors(None)
    return resolve_executors([s.strip() for s in spec.split(",") if s.strip()])


def build_forward(cfg_name: str, batch: int, seq: int):
    from thunder_tpu.core import dtypes
    from thunder_tpu.core.pytree import tree_flatten
    from thunder_tpu.executors.passes import transform_for_execution
    from thunder_tpu.models import gpt as m

    cfg = m.name_to_config(cfg_name)
    t0 = time.perf_counter()
    params = m.init_params(cfg, dtype=dtypes.bfloat16, device_init=True, seed=0)
    init_s = time.perf_counter() - t0
    idx = np.random.RandomState(0).randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32)

    t0 = time.perf_counter()
    comp = _trace_claim(lambda p, i: m.forward(p, i, cfg), (params, idx))
    extrace = transform_for_execution(comp, _executors())
    trace_s = time.perf_counter() - t0
    flat_args, _ = tree_flatten(((params, idx), {}))
    return extrace.python_callable(), flat_args, init_s, trace_s, extrace


def build_train(cfg_name: str, batch: int, seq: int):
    """One full training step (fw+bw+SGD) as a single donated-params XLA
    executable, matching the reference's train.py workload: bf16-true,
    torch.optim.SGD(lr=6e-4, weight_decay=0.1) — no momentum state, which
    is what lets the 3B model train on a 16 GB chip."""
    import jax
    import jax.numpy as jnp

    from thunder_tpu.core import dtypes
    from thunder_tpu.core.pytree import tree_flatten
    from thunder_tpu.executors.passes import transform_for_execution
    from thunder_tpu.models import gpt as m
    from thunder_tpu.transforms.autodiff import forward_and_backward_from_trace
    from thunder_tpu.transforms.rematerialization import rematerialize_forward_and_backward

    cfg = m.name_to_config(cfg_name)
    t0 = time.perf_counter()
    params = m.init_params(cfg, dtype=dtypes.bfloat16, device_init=True, seed=0)
    init_s = time.perf_counter() - t0
    rng = np.random.RandomState(0)
    idx = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    tgt = np.roll(idx, -1, axis=1).astype(np.int32)

    t0 = time.perf_counter()
    from thunder_tpu.transforms.attention_residuals import save_sdpa_residuals

    comp = _trace_claim(lambda p, i, t: m.loss_fn(p, i, t, cfg), (params, idx, tgt))
    fw, bw = forward_and_backward_from_trace(comp)
    executors = _executors()
    fw, bw = save_sdpa_residuals(fw, bw, executors)
    fw, bw = rematerialize_forward_and_backward(fw, bw)
    # comm_schedule: the certificate-driven collective-overlap scheduler
    # (ISSUE 13) — a strict no-op on the single-host traces (no collective
    # sites), recorded in the compile-phase dict so the committed round
    # proves the pass is wired into this path too.
    fw_ex = transform_for_execution(fw, executors, comm_schedule=True)
    bw_ex = transform_for_execution(bw, executors, comm_schedule=True)
    fw_fn = fw_ex.python_callable()
    bw_fn = bw_ex.python_callable()
    trace_s = time.perf_counter() - t0

    # Static planner overhead (ISSUE 10): liveness plan + collective-schedule
    # certificate over the claimed fw/bw traces, timed so the planner shows
    # up in the committed compile-phase record like any other compile phase.
    t0 = time.perf_counter()
    from thunder_tpu.analysis import liveness as live_mod
    from thunder_tpu.analysis import schedule as sched_mod

    peak = 0
    for trc in (fw_ex, bw_ex):
        peak = max(peak, live_mod.plan_liveness(trc, include_rows=False).peak_bytes)
        sched_mod.stamp(trc)
    predicted_peak_bytes = int(peak)
    static_analysis_s = time.perf_counter() - t0

    flat_params, _ = tree_flatten((params,))

    def step(flat_p, i, t):
        loss, saved = fw_fn(*flat_p, i, t)
        ct = jnp.ones((), dtype=loss.dtype)
        grads = bw_fn(*saved, ct)
        # torch.optim.SGD semantics: g += wd*p, p -= lr*g (bf16-true).
        new_p = [
            (p - LR * (g.astype(p.dtype) + WD * p)).astype(p.dtype)
            for p, g in zip(flat_p, grads)
        ]
        return new_p, loss

    t0 = time.perf_counter()
    jfn, flat_params = _stage_step(step, flat_params, idx, tgt)
    stage_s = time.perf_counter() - t0
    # The comm scheduler tags only traces it touched; single-host fw/bw
    # carry no collective sites, so 0 moves is the expected committed value.
    comm_moves = sum(
        (trc.tags.get("comm_schedule") or {}).get("moves", 0)
        for trc in (fw_ex, bw_ex)
    )
    return (jfn, flat_params, idx, tgt, init_s, trace_s, stage_s,
            static_analysis_s, predicted_peak_bytes, comm_moves)


def _stage_step(step, flat_params, idx, tgt):
    """Stage the train step with compiler-chosen (AUTO) parameter layouts.

    With default row-major arg layouts XLA re-lays-out the weight matrices
    EVERY iteration (~25-45 ms/step of pure copies at 3B scale — measured in
    the r4 profile: 45.7 ms/iter 'data formatting', dominated by
    bf16[9600,3200]-style param copies). AUTO layouts let the compiler pick
    the layouts it wants, and the params are device_put into them once,
    outside the timed loop. Opt out with THUNDER_BENCH_AUTOLAYOUT=0.
    """
    import os

    import jax

    if os.environ.get("THUNDER_BENCH_AUTOLAYOUT", "1") == "0":
        return jax.jit(step, donate_argnums=(0,)), flat_params
    # No fallback to default layouts: a step that silently pays the relayout
    # copies is a different measurement under the same name.
    from jax.experimental.layout import Format, Layout

    auto = Format(Layout.AUTO)
    jitted = jax.jit(
        step,
        donate_argnums=(0,),
        in_shardings=([auto] * len(flat_params), auto, auto),
        out_shardings=([auto] * len(flat_params), auto),
    )
    compiled = jitted.lower(flat_params, idx, tgt).compile()
    in_fmts = compiled.input_formats[0]
    out_fmts = compiled.output_formats
    # The loop feeds outputs back as inputs: layouts must round-trip.
    if str(out_fmts[0]) != str(in_fmts[0]):
        raise RuntimeError("AUTO param layouts don't round-trip through the step")
    flat_params = [jax.device_put(p, f) for p, f in zip(flat_params, in_fmts[0])]
    return compiled, flat_params


def _bench_forward():
    import os

    import jax

    flat_fn, flat_args, init_s, trace_s, extrace = build_forward("open_llama_3b", FWD_B, FWD_T)
    t0 = time.perf_counter()
    if os.environ.get("THUNDER_BENCH_AUTOLAYOUT", "1") == "0":
        jfn = jax.jit(flat_fn)
    else:
        from jax.experimental.layout import Format, Layout

        auto = Format(Layout.AUTO)
        jitted = jax.jit(flat_fn, in_shardings=tuple(auto for _ in flat_args))
        compiled = jitted.lower(*flat_args).compile()
        flat_args = [jax.device_put(a, f) for a, f in zip(flat_args, compiled.input_formats[0])]
        jfn = compiled

    def run():
        out = jfn(*flat_args)
        return float(np.asarray(out[0, 0, 0]))

    run()
    compile_s = time.perf_counter() - t0
    # Async-dispatch 5 forwards, sync once: no host round-trip per forward
    # in the timed window.
    run()
    t0 = time.perf_counter()
    outs = [jfn(*flat_args) for _ in range(5)]
    _ = float(np.asarray(outs[-1][0, 0, 0]))
    avg = (time.perf_counter() - t0) / 5.0
    print(f"# fwd param-init: {init_s:.1f}s trace+claim: {trace_s:.1f}s compile: {compile_s:.1f}s "
          f"avg of 5 batched-dispatch runs: {avg:.4f}s",
          file=sys.stderr)
    return avg, trace_s, compile_s, jfn, flat_args, extrace


def _bench_attribution(jfn, flat_args, steps: int = 2, trace=None, top_k: int = 10):
    """Per-op device-time attribution of the forward (ISSUE 5): two
    profiler-bracketed dispatches, HLO scopes mapped back to trace lines.
    Returns {"coverage_pct", "top5", "topk", "_join"}. A backend with no
    profiler plugin, or a profile with no scopes, raises: a bench line with a
    hole where the attribution should be reads as a measurement.

    ``top5`` keeps the original print-table shape; ``topk`` (ISSUE 19) is
    the structured per-op series — measured us joined against the static
    cost model's roofline ceiling when ``trace`` (the execution TraceCtx)
    is given — that history tooling and the roofline-ledger gate consume
    from the BENCH json. ``_join`` is the in-process PerfJoin for the
    ROOFLINE_r*.json writer; main() pops it before serializing."""
    import tempfile

    import thunder_tpu as ttpu
    from thunder_tpu.analysis.cost import trace_cost
    from thunder_tpu.observability.attribution import attribute, join_cost_attribution

    hlo_text = jfn.as_text() if hasattr(jfn, "as_text") else None
    trace_dir = tempfile.mkdtemp(prefix="thunder_bench_attr_")
    res = ttpu.profile(lambda: jfn(*flat_args), trace_dir=trace_dir, steps=steps, warmup=0)
    if not res["profiler"]:
        raise RuntimeError("attribution: no profiler plugin on this backend")
    # profile() already attributed in-process when the event names carry
    # scopes (TPU); re-parse only for raw-op-name backends needing the
    # HLO join.
    attr = res["attribution"]
    if attr is None:
        attr = attribute(trace_dir, hlo_text=hlo_text)
    if not attr.by_line:
        raise RuntimeError("attribution: no L<idx>.<sym> scopes in the profile "
                           "(THUNDER_TPU_ANNOTATE_TRACES not active at codegen?)")
    cost = trace_cost(trace, None) if trace is not None else None
    join = join_cost_attribution(attr, cost, steps=steps)
    top5 = [
        {
            "line": ref.label,
            "sym": ref.sym,
            "pass": ref.pass_name,
            "us_per_step": round(us / steps, 1),
            "share_pct": round(us / attr.device_busy_us * 100.0, 1),
        }
        for ref, us in attr.top(5)
    ]
    topk = [
        {
            "line": r.label,
            "sym": r.sym,
            "pass": r.pass_name,
            "us_per_step": round(r.measured_us, 1),
            "share_pct": round(r.share * 100.0, 1),
            "flops": r.flops,
            "bytes": r.bytes_moved,
            "roofline_us": (round(r.roofline_us, 1)
                            if r.roofline_us is not None else None),
            "achieved_frac": (round(r.efficiency, 4)
                              if r.efficiency is not None else None),
            "bound": r.bound,
        }
        for r in join.rows[:top_k]
    ]
    print("# fwd attribution (top 5 of "
          f"{attr.device_busy_us / steps / 1e3:.1f} ms device-busy/step, "
          f"{attr.coverage * 100:.0f}% attributed):", file=sys.stderr)
    for row in top5:
        print(f"#   {row['line']:<40} {row['us_per_step']:>9}us {row['share_pct']:>5}%",
              file=sys.stderr)
    return {"coverage_pct": round(attr.coverage * 100.0, 1),
            "top5": top5, "topk": topk, "_join": join}


def _op_flat_key(label: str, taken) -> str:
    """Flatten one op scope into a stable per-round metric key:
    ``L154.exp#Delete_Last_Used`` -> ``op_L154_exp`` (pass provenance
    dropped — line+sym identify the op across rounds; rare collisions get
    a numeric suffix so no row silently shadows another)."""
    import re

    scope = label.split("#", 1)[0]
    key = "op_" + re.sub(r"[^0-9A-Za-z]+", "_", scope).strip("_")
    base, n = key, 2
    while key in taken:
        key = f"{base}_{n}"
        n += 1
    taken.add(key)
    return key


def _roofline_result(ledger, *, metric: str, device_spec, probes: int,
                     coverage_pct, flat_top_k: int = 12) -> dict:
    """One ROOFLINE_r*.json round from a folded ledger: the full per-op
    ``rows`` series (the committed schema of observability/roofline.py's
    ``ROW_FIELDS``) plus top-k per-op numerics flattened to top level —
    ``op_<line>_<sym>_us`` / ``_achieved_frac`` — which is what
    scripts/perf_report.py's direction-aware history gate actually
    compares (exposed time up / achieved fraction down on a named op
    fails the gate)."""
    from thunder_tpu.observability.roofline import ROW_FIELDS

    rows = ledger.snapshot()["rows"]
    busy_ms = sum(r["measured_us"] for r in rows) / 1e3
    schema_ok = all(set(r) == set(ROW_FIELDS) for r in rows)
    result = {
        "metric": metric,
        "value": round(busy_ms, 4),
        "unit": "ms_device_busy_per_step",
        "device_spec": device_spec,
        "probes": probes,
        "roofline_rows": len(rows),
        "roofline_schema_ok": 1 if schema_ok else 0,
        "roofline_coverage_pct": coverage_pct,
        "rows": rows,
    }
    taken: set = set()
    for r in rows[:flat_top_k]:
        key = _op_flat_key(r["label"], taken)
        result[f"{key}_us"] = r["measured_us"]
        if r["achieved_frac"] is not None:
            result[f"{key}_achieved_frac"] = r["achieved_frac"]
    return result


def _write_roofline_round(join, out_path: str, *, metric: str, probes: int = 1):
    """Fold a PerfJoin (or several — ``probes`` says how many) into a fresh
    ledger and commit it as a ROOFLINE round."""
    from thunder_tpu.observability.roofline import RooflineLedger

    ledger = RooflineLedger()
    joins = join if isinstance(join, list) else [join]
    for j in joins:
        ledger.fold(j)
    last = joins[-1]
    device_spec = (last.cost.device.name
                   if getattr(last, "cost", None) is not None else None)
    result = _roofline_result(
        ledger, metric=metric, device_spec=device_spec, probes=probes,
        coverage_pct=round(last.attribution.coverage * 100.0, 1))
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
        f.write("\n")
    print(f"# roofline round: {result['roofline_rows']} op rows "
          f"({result['value']:.3f} ms device-busy/step) -> {out_path}",
          file=sys.stderr)
    return result


def _load_prev_round():
    """(label, metrics) of the newest committed BENCH_r*.json next to this
    script, or (None, None) — bench.py prints per-metric deltas against it so
    a regression is visible at the moment it happens, not five rounds later."""
    import glob
    import os

    here = os.path.dirname(os.path.abspath(__file__))
    paths = sorted(glob.glob(os.path.join(here, "BENCH_r*.json")))
    if not paths:
        return None, None
    sys.path.insert(0, os.path.join(here, "scripts"))
    try:
        from perf_report import load_round

        return load_round(paths[-1])
    except Exception as e:
        print(f"# prev-round load failed ({type(e).__name__}: {e})", file=sys.stderr)
        return None, None


def _bench_train():
    # Compile-phase decomposition of the train compile total (ISSUE 8): the
    # jax monitoring taps (api._jax_cache_counts) split the opaque
    # train_xla_compile_s into real backend-compile seconds vs persistent-
    # cache deserialize — the distinction the r4→r5 doubling needed
    # (BENCHMARKS.md "compile-phase diagnosis").
    from thunder_tpu.api import _jax_cache_counts

    jax_c0 = _jax_cache_counts()
    (jfn, flat_params, idx, tgt, init_s, trace_s, stage_s,
     static_s, predicted_peak, comm_moves) = build_train("open_llama_3b", TRAIN_B, TRAIN_T)

    t0 = time.perf_counter()
    flat_params, loss = jfn(flat_params, idx, tgt)
    loss0 = float(np.asarray(loss))
    compile_s = stage_s + time.perf_counter() - t0
    jax_c1 = _jax_cache_counts()
    phases = {
        "trace_claim_s": round(trace_s, 2),
        # The static planner suite (ISSUE 10): liveness + schedule
        # certification seconds over the claimed fw/bw traces, and the
        # plan's predicted per-device peak — visible (and gated via the
        # committed record) like any other compile phase.
        "static_analysis_s": round(static_s, 3),
        "predicted_peak_bytes": predicted_peak,
        "comm_schedule_moves": comm_moves,
        "staging_s": round(stage_s, 2),
        "xla_backend_compile_s": round(jax_c1["backend_compile_s"] - jax_c0["backend_compile_s"], 2),
        "persistent_cache_get_s": round(jax_c1["cache_get_s"] - jax_c0["cache_get_s"], 2),
        "persistent_cache_hits": jax_c1["hits"] - jax_c0["hits"],
        "persistent_cache_misses": jax_c1["misses"] - jax_c0["misses"],
    }
    print(f"# train compile phases: {phases}", file=sys.stderr)

    # Three timing protocols, all reported (ADVICE r3 / VERDICT r4: the A100
    # baseline constant comes from the reference's train.py, whose timed
    # region reads loss.item() every iteration):
    #  - async: 45 iters chained through the donated params, ONE final sync.
    #  - synced: every iteration's loss reaches the host as a Python float
    #    (the reference loop's observable behavior), with the read of loss
    #    i-1 overlapped with the dispatch of iter i — the "overlap the host
    #    read with the next dispatch" fix from VERDICT r4.
    #  - strict: block_until_ready on each loss before dispatching the next
    #    step — serializes on the host round-trip; the other-side bound.
    t0 = time.perf_counter()
    for _ in range(45):
        flat_params, loss = jfn(flat_params, idx, tgt)
    loss_last = float(np.asarray(loss))  # one sync at the end
    total = time.perf_counter() - t0
    avg = total / 45.0

    # Synced protocol: every iteration's loss is fetched to the host as a
    # Python float — the reference loop's observable behavior — but the
    # fetch of loss i-1 is overlapped with the dispatch of iter i (the read
    # rides under device compute instead of serializing on the host round
    # trip). copy_to_host_async starts the D2H transfer the moment the loss
    # buffer is ready.
    n_sync = 20
    host_losses = []
    prev = None
    t0 = time.perf_counter()
    for _ in range(n_sync):
        flat_params, loss = jfn(flat_params, idx, tgt)
        loss.copy_to_host_async()
        if prev is not None:
            host_losses.append(float(np.asarray(prev)))
        prev = loss
    host_losses.append(float(np.asarray(prev)))
    synced_avg = (time.perf_counter() - t0) / n_sync
    assert len(host_losses) == n_sync and all(np.isfinite(l) for l in host_losses)

    # Strict variant (block_until_ready on every loss before the next
    # dispatch): pays the full host round-trip per step; reported for
    # transparency as the from-the-other-side bound.
    t0 = time.perf_counter()
    n_strict = 10
    for _ in range(n_strict):
        flat_params, loss = jfn(flat_params, idx, tgt)
        loss.block_until_ready()
    strict_avg = (time.perf_counter() - t0) / n_strict
    print(
        f"# train param-init: {init_s:.1f}s trace+claim: {trace_s:.1f}s compile: {compile_s:.1f}s "
        f"45 iters: {total:.2f}s avg iter: {avg:.4f}s (synced {synced_avg:.4f}s, "
        f"strict {strict_avg:.4f}s) loss {loss0:.3f}->{loss_last:.3f}",
        file=sys.stderr,
    )
    assert np.isfinite(loss_last) and loss_last < loss0, (loss0, loss_last)
    return avg, synced_avg, strict_avg, total, trace_s, compile_s, phases


def _bench_cache():
    """Dispatch-path microbench: recompiles under bucketed symbolic caching
    and the warm O(1) lookup cost (ISSUE 2 observability — the driver's JSON
    line now tracks recompile storms and dispatch latency directly)."""
    import thunder_tpu as ttpu
    import thunder_tpu.clang as clang

    def f(x):
        return clang.sum(clang.tanh(x))

    jf = ttpu.jit(f, cache="symbolic values", executors=["jax"],
                  symbolic_dims={0: (0,)}, buckets={"batch": "pow2"})
    xs = {b: np.ones((b, 64), np.float32) for b in range(1, 9)}
    for b, x in xs.items():  # 8 batch sizes → one compile per pow2 bucket
        jf(x)
    for b, x in xs.items():  # warm sweep: learns every O(1) key
        jf(x)

    cs = ttpu.compile_stats(jf)
    n_warm = 200
    lookup_ns0 = cs.cache_lookup_ns
    for _ in range(n_warm):
        jf(xs[8])
    lookup_us = (cs.cache_lookup_ns - lookup_ns0) / 1e3 / n_warm
    info = ttpu.cache_info(jf)
    print(f"# cache: {info['compiles']} compiles for 8 batch sizes, "
          f"{info['fast_hits']} O(1) hits, warm lookup {lookup_us:.1f}us",
          file=sys.stderr)
    return info["recompiles"], lookup_us


def _bench_obs_overhead():
    """GPT-block dispatch overhead of the observability layer (ISSUE 4
    acceptance budgets: <1% with everything disabled, <5% with metrics on).

    A naive A/B wall-clock comparison cannot resolve the effect: the metric
    block costs single-digit microseconds against a millisecond-scale
    GPT-block call, far below host timing noise. So this measures the two
    factors directly and composes them:

    - the warm per-call dispatch+execute time of a jitted gpt-tiny forward
      (min over reps — the noise floor estimate), and
    - the exact per-call cost of the observability code on that path:
      with metrics DISABLED, the guard checks alone; with metrics ENABLED,
      guard + counter + two histogram observations (the fn_ hit-path block).
    """
    import jax

    import thunder_tpu as ttpu
    import thunder_tpu.monitor as monitor
    from thunder_tpu.core import dtypes
    from thunder_tpu.models import gpt as m
    from thunder_tpu.observability import metrics as obsm

    cfg = m.name_to_config("gpt-tiny")
    params = m.init_params(cfg, dtype=dtypes.float32, seed=0)
    idx = np.random.RandomState(0).randint(0, cfg.vocab_size, (2, 64)).astype(np.int32)
    jf = ttpu.jit(lambda p, i: m.forward(p, i, cfg), executors=["jax"])

    def timed(n=100):
        out = None
        t0 = time.perf_counter()
        for _ in range(n):
            out = jf(params, idx)
        if isinstance(out, jax.Array):
            out.block_until_ready()
        return (time.perf_counter() - t0) / n

    jf(params, idx)  # compile
    timed(20)  # warm the dispatch fast path
    dispatch_us = min(timed() for _ in range(5)) * 1e6

    was_enabled = monitor.enabled()
    N = 50_000

    def block_ns(n):
        # The exact per-call observability work on the warm hit path
        # (api.fn_): one enabled() guard when off; guard + labelled counter
        # inc + two histogram observations when on.
        t0 = time.perf_counter()
        for _ in range(n):
            if obsm.enabled():
                obsm.CACHE_HITS.inc(kind="fast")
                obsm.CACHE_LOOKUP_US.observe(12.0)
                obsm.DISPATCH_US.observe(120.0)
        return (time.perf_counter() - t0) / n * 1e9

    monitor.disable()
    disabled_ns = block_ns(N)
    monitor.enable()
    enabled_ns = block_ns(N)
    # The N synthetic samples above must not masquerade as real traffic in
    # the bench's exported metrics snapshot.
    monitor.reset()
    (monitor.enable if was_enabled else monitor.disable)()

    # Ops plane (ISSUE 15): its steady-state cost is one event tap (flight
    # ring append + detector consume) per emitted record — one step_time
    # per training step. Measured the same composed way: exact per-event
    # cost × events-per-step over the step time, on vs off (off = the one
    # module-global truth test the emit path always pays).
    from thunder_tpu.observability import events as obs_events
    from thunder_tpu.observability import opsplane

    def event_ns(n=20_000):
        t0 = time.perf_counter()
        for _ in range(n):
            obs_events.emit_event("step_time", fn="ops_bench", step=0, s=0.01)
        return (time.perf_counter() - t0) / n * 1e9

    # Tap-level A/B: clearing/restoring the taps measures the per-event
    # cost without tearing down a live plane's server (an autostarted
    # THUNDER_TPU_OPS_PORT plane must keep serving through the bench).
    saved_taps, saved_recorder = obs_events.ops_taps()
    obs_events.set_ops_taps((), recorder=None)
    ops_off_ns = event_ns()
    if saved_taps:
        obs_events.set_ops_taps(saved_taps, recorder=saved_recorder)
        ops_on_ns = event_ns()
    else:
        opsplane.enable(serve=False)
        ops_on_ns = event_ns()
        opsplane.disable()
    ops_off_pct = ops_off_ns / 1e3 / dispatch_us * 100.0
    ops_pct = ops_on_ns / 1e3 / dispatch_us * 100.0

    disabled_pct = disabled_ns / 1e3 / dispatch_us * 100.0
    metrics_pct = enabled_ns / 1e3 / dispatch_us * 100.0
    print(f"# obs overhead: gpt-tiny warm dispatch {dispatch_us:.1f}us; obs code "
          f"{disabled_ns:.0f}ns/call disabled ({disabled_pct:.3f}%), "
          f"{enabled_ns:.0f}ns/call metrics-on ({metrics_pct:.3f}%); ops plane "
          f"{ops_off_ns:.0f}ns/event off ({ops_off_pct:.4f}%), "
          f"{ops_on_ns:.0f}ns/event on ({ops_pct:.4f}%)", file=sys.stderr)
    return dispatch_us, disabled_pct, metrics_pct, ops_off_pct, ops_pct


def main() -> None:
    import os

    import thunder_tpu.monitor as monitor
    from thunder_tpu.api import _ensure_runtime
    from thunder_tpu.benchmarks import device_description, peak_tflops
    from thunder_tpu.observability import metrics as obsm

    device = device_description()
    if device["platform"] != "tpu":
        # Every number below is a device metric; a CPU run has none to give.
        raise SystemExit(f"bench.py measures a TPU; jax reports {device} "
                         "(run it on the chip)")

    # Annotated codegen is free at steady state (named_scope only shapes HLO
    # metadata during jit tracing) and is what lets the profiler rows map
    # back to trace lines for the attribution table below.
    os.environ.setdefault("THUNDER_TPU_ANNOTATE_TRACES", "1")

    _ensure_runtime()  # torch-faithful dtypes + persistent XLA compile cache
    (obs_dispatch_us, obs_disabled_pct, obs_metrics_pct,
     ops_off_pct, ops_pct) = _bench_obs_overhead()
    # Metrics stay ON for the rest of the run so the JSON line carries a
    # populated observability snapshot (ISSUE 4: BENCH_*.json embeds it).
    monitor.enable()
    recompile_count, lookup_us = _bench_cache()
    (fwd_avg, fwd_trace_s, fwd_compile_s, fwd_jfn, fwd_args,
     fwd_extrace) = _bench_forward()
    (train_avg, train_synced, train_strict, train_total,
     train_trace_s, train_compile_s, train_phases) = _bench_train()
    # Profile LAST among the compiling benches: the gated compile-seconds
    # metrics must be measured before the process runs a profiler session,
    # so a future profiler-side effect can never contaminate them (the
    # r4->r5 diagnosis had to refute exactly this hypothesis by experiment
    # — see BENCHMARKS.md "compile-phase diagnosis"; ordering it out keeps
    # the refutation permanent).
    attribution = _bench_attribution(fwd_jfn, fwd_args, trace=fwd_extrace)
    # The roofline per-op series (ISSUE 19): the same join, committed as a
    # ROOFLINE_r*.json round when the driver asks for one. Pop the live
    # PerfJoin either way — it is not JSON.
    fwd_join = attribution.pop("_join", None) if attribution else None
    roofline_out = os.environ.get("THUNDER_TPU_ROOFLINE_OUT")
    if roofline_out and fwd_join is not None:
        _write_roofline_round(fwd_join, roofline_out,
                              metric="roofline_open_llama_3b_fwd")
    # The end-to-end XLA compile totals as labelled histogram samples — the
    # metric whose 2x jump (r4->r5) per-pass ms could not see (ISSUE 5).
    obsm.XLA_COMPILE_S.observe(fwd_compile_s, cls="bench_forward")
    obsm.XLA_COMPILE_S.observe(train_compile_s, cls="bench_train_step")

    peak = peak_tflops()
    fwd_flops = 2.0 * N_PARAMS * FWD_B * FWD_T
    train_flops = 6.0 * N_PARAMS * TRAIN_B * TRAIN_T
    train_mfu = train_flops / train_avg / 1e12 / peak
    synced_mfu = train_flops / train_synced / 1e12 / peak
    fwd_mfu = fwd_flops / fwd_avg / 1e12 / peak
    # Hardware-neutral comparison: the reference's training MFU on its A100
    # (312 bf16 TFLOP/s peak) from the same FLOP model.
    ref_train_mfu = train_flops / REF_TRAIN_ITER_A100_S / 1e12 / 312.0

    result = {
        "device": device,
        "metric": "open_llama_3b_train_iter_b2_t2048",
        "value": round(train_avg, 4),
        "unit": "s",
        "vs_baseline": round(REF_TRAIN_ITER_A100_S / train_avg, 3),
        # HEADLINE comparison (VERDICT r4): synced protocol vs the
        # reference's synced protocol — every loss reaches the host.
        "train_synced_mfu_vs_ref_mfu": round(synced_mfu / ref_train_mfu, 3),
        "train_mfu_vs_ref_mfu": round(train_mfu / ref_train_mfu, 3),
        "ref_train_mfu_a100": round(ref_train_mfu, 3),
        "train_45iters_s": round(train_total, 2),
        "train_tokens_per_sec": round(TRAIN_B * TRAIN_T / train_avg),
        "train_mfu": round(train_mfu, 3),
        "train_synced_mfu": round(synced_mfu, 3),
        # Protocol disclosure: async = 45-iter chain, one final sync.
        # synced = every iteration's loss read on host as a float, the read
        # of loss i-1 overlapped with dispatch of iter i. strict = hard
        # block_until_ready per iter (pays the host round-trip per step).
        "timing_protocol": "async_45iter_chain_single_sync",
        "ref_timing_protocol": "per_iter_loss_sync (reference train.py)",
        "train_iter_synced_s": round(train_synced, 4),
        "train_iter_strict_sync_s": round(train_strict, 4),
        "fwd_b10_s": round(fwd_avg, 4),
        "fwd_vs_baseline": round(REF_FWD_A100_S / fwd_avg, 3),
        "fwd_mfu": round(fwd_mfu, 3),
        "fwd_trace_claim_s": round(fwd_trace_s, 1),
        "fwd_xla_compile_s": round(fwd_compile_s, 1),
        "train_trace_claim_s": round(train_trace_s, 1),
        "train_xla_compile_s": round(train_compile_s, 1),
        # Decomposition of the line above (ISSUE 8): backend-compile seconds
        # vs persistent-cache deserialize + hit/miss counts, so the next
        # compile-time swing names its phase instead of being one number.
        "train_compile_phases": train_phases,
        # Dispatch-path health (cache="symbolic values" over 8 batch sizes):
        # recompiles per sweep and the warm O(1) cache lookup cost.
        "recompile_count": recompile_count,
        "trace_cache_lookup_us": round(lookup_us, 1),
        # Observability layer (ISSUE 4): GPT-block warm dispatch time and
        # the measured overhead of the dispatch-path observability code with
        # the layer disabled vs metrics enabled, plus the process-wide
        # metrics snapshot accumulated over this bench run.
        "obs_gpt_block_dispatch_us": round(obs_dispatch_us, 1),
        "obs_disabled_overhead_pct": round(obs_disabled_pct, 4),
        "obs_metrics_overhead_pct": round(obs_metrics_pct, 4),
        # Live ops plane (ISSUE 15): per-event tap cost (flight ring +
        # detectors) composed over the warm dispatch at one event/step —
        # the < 1% acceptance budget with the plane ON, and the cost of the
        # bare module-global probe with it OFF.
        "ops_overhead_pct": round(ops_pct, 4),
        "ops_off_overhead_pct": round(ops_off_pct, 4),
        # Top-5 device-time attribution of the forward (None when the
        # backend has no profiler plugin): which trace lines eat the step.
        "attribution": attribution,
        "metrics": monitor.report_compact(),
    }

    # Deltas vs the newest committed round (ISSUE 5): a >10% regression on
    # any gated metric warns HERE, in the run that introduced it — the
    # committed-history gate (scripts/perf_report.py --history) is the
    # backstop, not the first line of defense. The keys are always present
    # (vs_rev=None, empty deltas on a fresh clone with no committed
    # BENCH_r*.json), so JSON consumers never need the glob to be non-empty.
    result["vs_rev"] = None
    result["deltas_vs_prev"] = {}
    result["regressions_vs_prev"] = []
    prev_label, prev_metrics = _load_prev_round()
    if prev_metrics:
        try:
            from perf_report import compare_rounds

            cur_cmp = dict(result)
            cur_cmp["_metric_name"] = result["metric"]
            deltas, regressions = compare_rounds(prev_metrics, cur_cmp, threshold=0.10)
            result["prev_round"] = prev_label
            result["vs_rev"] = prev_label  # the round every delta is against
            result["deltas_vs_prev"] = deltas
            result["regressions_vs_prev"] = regressions
            shown = {k: v for k, v in sorted(deltas.items(), key=lambda kv: -abs(kv[1]))[:8]}
            print(f"# deltas vs {prev_label}: " + ", ".join(
                f"{k} {v * 100:+.1f}%" for k, v in shown.items()), file=sys.stderr)
            for r in regressions:
                print(f"# WARNING: regression vs {prev_label}: {r}", file=sys.stderr)
        except Exception as e:
            print(f"# delta computation failed ({type(e).__name__}: {e})", file=sys.stderr)
    else:
        print("# no committed BENCH_r*.json history; deltas skipped "
              "(vs_rev=null)", file=sys.stderr)

    print(json.dumps(result))


def roofline_main(argv) -> None:
    """``python bench.py --roofline-out PATH [--model gpt-tiny] [--batch B]
    [--seq T] [--every N] [--probes K]`` — the light roofline-only bench
    (ISSUE 19): arm the duty-cycled RooflineSampler on a jitted forward,
    run ``every*probes`` steps so exactly ``probes`` of them profile, and
    commit the folded ledger as a ROOFLINE_r*.json per-op round. Small
    models on purpose: this path must run wherever CI does (CPU included),
    unlike the 3B main() workload; the env-driven
    THUNDER_TPU_ROOFLINE_OUT hook in main() covers the TPU bench."""
    import os

    os.environ.setdefault("THUNDER_TPU_ANNOTATE_TRACES", "1")

    def opt(name, default):
        return argv[argv.index(name) + 1] if name in argv else default

    out_path = opt("--roofline-out", "ROOFLINE_r01.json")
    model = opt("--model", "gpt-tiny")
    batch = int(opt("--batch", 2))
    seq = int(opt("--seq", 32))
    every = int(opt("--every", 2))
    probes = int(opt("--probes", 3))
    executors = opt("--executors", "jax").split(",")

    import thunder_tpu as ttpu
    from thunder_tpu.api import _ensure_runtime
    from thunder_tpu.core.pytree import tree_flatten
    from thunder_tpu.models import gpt as m
    from thunder_tpu.observability.roofline import RooflineSampler

    _ensure_runtime()
    cfg = m.name_to_config(model)
    params = m.init_params(cfg)
    idx = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    jfn = ttpu.jit(lambda p, i: m.forward(p, i, cfg), executors=executors)
    jfn(params, idx)  # compile outside the sampled loop

    sampler = RooflineSampler(jfn, every=every)
    for _ in range(every * probes):
        sampler.maybe_sample(jfn, params, idx)
    if sampler.probes != probes or len(sampler.ledger) == 0:
        print(f"# roofline bench failed: {sampler.probes}/{probes} probes, "
              f"{len(sampler.ledger)} ledger ops", file=sys.stderr)
        raise SystemExit(1)
    device_spec = (sampler._cost.device.name
                   if sampler._cost is not None else None)
    coverage = (round(sampler.last_coverage * 100.0, 1)
                if sampler.last_coverage is not None else None)
    result = _roofline_result(
        sampler.ledger, metric=f"roofline_{model.replace('-', '_')}_fwd",
        device_spec=device_spec, probes=sampler.probes,
        coverage_pct=coverage)
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
        f.write("\n")
    print(sampler.ledger.format(top_k=10), file=sys.stderr)
    print(f"# roofline round: {result['roofline_rows']} op rows -> {out_path}",
          file=sys.stderr)
    print(json.dumps({k: v for k, v in result.items() if k != "rows"}))


if __name__ == "__main__":
    if "--roofline-out" in sys.argv:
        roofline_main(sys.argv[1:])
        raise SystemExit(0)
    main()
