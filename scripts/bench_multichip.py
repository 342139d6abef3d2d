#!/usr/bin/env python
"""Multichip benchmark: the FSDP×TP training step, measured.

The distributed half of the bench story (single-host: ``bench.py``): run one
full training step (fw+bw+optimizer, ``parallel.build_train_step``) over an
n-device mesh — a virtual 8-device CPU mesh anywhere, real chips when the
process already owns them — and measure what ``MULTICHIP_r*.json`` never
recorded: per-step wall time under the three timing protocols, aggregate
MFU from the PR 5 cost model, per-collective device time split into
hidden-under-compute vs exposed-on-the-critical-path, and the compile-phase
decomposition of the multichip XLA compile.

Two workloads per run:

1. **FSDP×TP step** (SPMD partitioner inserts the collectives): step
   timings, MFU, and per-collective-family measured wire time from a
   profiled run (``observability.attribution`` classifies ``all-gather``/
   ``all-reduce``/... rows and computes the overlap split).
2. **Explicit-collective FSDP×TP step** (trace-level ``dist_prims`` under
   ``shard_map``): every collective carries an ``L<idx>.<sym>#<pass>``
   scope. The step runs unscheduled (measured lane table → per-class ICI
   calibration), then through the certificate-driven comm scheduler
   (``transforms/comm_schedule.py``), and the committed overlap table joins
   the scheduler's static per-site hidden/exposed prediction against the
   measured lane segmentation — ROADMAP item 2's overlap work, landed.

Output: one JSON line on stdout (the committed ``MULTICHIP_BENCH_r*.json``
series), consumed by ``scripts/perf_report.py --history
MULTICHIP_BENCH_r*.json [--gate]`` with the same direction-aware deltas and
noise floors as the single-host series. ``scripts/lint_traces.py
--multichip`` runs a reduced-iteration smoke of this bench in CI.

Usage::

    python scripts/bench_multichip.py --devices 4      # the four-chip host
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python scripts/bench_multichip.py --devices 8  # functional run, CPU mesh

With fewer devices than asked it exits non-zero and says how many jax
reports; it never re-executes itself onto a CPU mesh. The JSON line carries
``device`` (platform, kind, count): on a CPU mesh its times are counts of
host work, not device metrics.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402


def _log(msg: str) -> None:
    print(f"# {msg}", file=sys.stderr)


def mesh_factors(n: int) -> dict:
    """Factor n devices into fsdp × tp, fsdp-first (the ROADMAP item 2
    shape): 8 → fsdp4·tp2, 4 → fsdp2·tp2, 2 → fsdp2, odd → fsdp=n."""
    tp = 2 if n % 2 == 0 and n > 2 else 1
    return {"fsdp": n // tp, "tp": tp}


def _executors():
    """Default to the jax executor: Pallas kernels run in interpret mode on
    the CPU mesh (orders of magnitude slower, and not what multichip timing
    should measure). THUNDER_BENCH_EXECUTORS overrides, as in bench.py."""
    spec = os.environ.get("THUNDER_BENCH_EXECUTORS")
    if not spec:
        return ["jax"]
    return [s.strip() for s in spec.split(",") if s.strip()]


# =============================================================================
# Workload 1: FSDP×TP training step (SPMD partitioner collectives)
# =============================================================================


def bench_fsdp_tp(args, result: dict) -> None:
    import thunder_tpu as ttpu
    from thunder_tpu.analysis.cost import resolve_device_spec, trace_cost
    from thunder_tpu.api import _jax_cache_counts
    from thunder_tpu.core import dtypes
    from thunder_tpu.models import gpt as m
    from thunder_tpu.observability.attribution import scope_map_of
    from thunder_tpu.parallel import build_train_step, make_mesh
    from thunder_tpu.parallel.sharding import gpt_param_specs

    n = args.devices
    factors = mesh_factors(n)
    mesh = make_mesh(**factors)
    cfg = m.name_to_config(args.model)
    params = m.init_params(cfg, dtype=dtypes.float32, seed=0)
    rng = np.random.RandomState(0)
    B = args.batch or max(2, 2 * factors["fsdp"])
    idx = rng.randint(0, cfg.vocab_size, (B, args.seq)).astype(np.int32)
    tgt = np.roll(idx, -1, axis=1).astype(np.int32)
    specs = gpt_param_specs(cfg, mesh)

    jax_c0 = _jax_cache_counts()
    t0 = time.perf_counter()
    step, opt, extrace = build_train_step(
        cfg, params, idx, tgt, mesh=mesh, param_specs=specs, lr=1e-3,
        executors=_executors(), donate=False, return_extrace=True,
    )
    trace_s = time.perf_counter() - t0

    # Static planner overhead on the multichip trace (ISSUE 10): liveness
    # plan + schedule certificate, timed so the new static_analysis compile
    # phase is visible in the committed multichip record like any other
    # phase. The recorded peak divides INPUT params by their PartitionSpecs;
    # intermediates have no trace-level sharding (the SPMD partitioner
    # decides), so the number is an upper bound on per-device HBM —
    # activations charged at global shape.
    t0 = time.perf_counter()
    try:
        from thunder_tpu.analysis import liveness as live_mod
        from thunder_tpu.analysis import schedule as sched_mod

        divisors = live_mod.arg_divisors_from_specs(extrace, specs, mesh=mesh)
        plan = live_mod.plan_liveness(
            extrace, arg_divisors=divisors, include_rows=False
        )
        sched_mod.stamp(extrace)
        predicted_peak = int(plan.peak_bytes)
    except Exception:
        predicted_peak = None
    static_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    p, o, loss = step(params, opt, idx, tgt)
    loss.block_until_ready()
    compile_s = trace_s + time.perf_counter() - t0
    jax_c1 = _jax_cache_counts()
    loss0 = float(np.asarray(loss))
    assert np.isfinite(loss0), loss0

    # Async chain: iters steps threaded through the returned state, one sync.
    for _ in range(2):
        p, o, loss = step(p, o, idx, tgt)
    loss.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(args.iters):
        p, o, loss = step(p, o, idx, tgt)
    loss_last = float(np.asarray(loss))
    iter_s = (time.perf_counter() - t0) / args.iters

    # Synced: every loss reaches the host before the next dispatch overlap
    # (bench.py's protocol); strict: hard block per step.
    n_sync = max(3, args.iters // 2)
    t0 = time.perf_counter()
    prev = None
    for _ in range(n_sync):
        p, o, loss = step(p, o, idx, tgt)
        if prev is not None:
            float(np.asarray(prev))
        prev = loss
    float(np.asarray(prev))
    synced_s = (time.perf_counter() - t0) / n_sync
    t0 = time.perf_counter()
    for _ in range(n_sync):
        p, o, loss = step(p, o, idx, tgt)
        loss.block_until_ready()
    strict_s = (time.perf_counter() - t0) / n_sync
    assert np.isfinite(loss_last), loss_last

    if args.resilience_overhead:
        # Steady-state cost of the mesh-wide fault-tolerance layer (ISSUE 9):
        # each guarded dispatch runs under the collective watchdog (worker
        # thread + join) and the step's new state through the SDC
        # replica-checksum guard. Target <2% at production step times;
        # docs/robustness.md documents the knobs (check_every amortizes the
        # checksum; fully-sharded leaves cost nothing).
        from thunder_tpu.resilience.watchdog import SDCGuard, guard_call

        med = lambda xs: sorted(xs)[len(xs) // 2]  # noqa: E731
        guard = SDCGuard(check_every=1)
        # The guard's added work is strictly additive to a step (the
        # watchdog adds one worker-thread spawn+join per dispatch; the SDC
        # check runs on the host after the step syncs), so each component
        # is measured DIRECTLY and the overhead derived over the median
        # guarded step — an emulated CPU mesh's steps jitter ±50% under a
        # shared scheduler, which drowns any loop-vs-loop delta of a
        # percent-scale cost (the failed protocol r02 replaced).
        plain, checks = [], []
        for _ in range(max(6, n_sync)):
            t0 = time.perf_counter()
            p, o, loss = guard_call(step, (p, o, idx, tgt),
                                    fn_name="train_step", timeout_s=120.0)
            loss.block_until_ready()
            tc = time.perf_counter()
            plain.append(tc - t0)
            guard.check_state((p, o))
            checks.append(time.perf_counter() - tc)
        spawn = []
        noop = lambda: None  # noqa: E731
        for _ in range(50):
            t0 = time.perf_counter()
            guard_call(noop, (), fn_name="noop", timeout_s=120.0)
            spawn.append(time.perf_counter() - t0)
        step_s, check_s, spawn_s = med(plain), med(checks), med(spawn)
        overhead_pct = ((check_s + spawn_s) / step_s * 100.0) if step_s else 0.0
        result["resilience_iter_s"] = round(step_s + check_s + spawn_s, 4)
        result["resilience_overhead_pct"] = round(overhead_pct, 2)
        result["sdc_check_us_per_step"] = round(check_s * 1e6, 1)
        result["watchdog_dispatch_us"] = round(spawn_s * 1e6, 1)
        _log(f"resilience overhead: sdc check {check_s * 1e6:.0f}us + watchdog "
             f"{spawn_s * 1e6:.0f}us over a {step_s * 1e3:.1f}ms median step "
             f"= {overhead_pct:+.2f}%")

        # Tiered-checkpoint hot-path stall (ISSUE 14): the device→host
        # snapshot of the full train state (params + opt) — the ONLY cost
        # a snapshot_every=1 cadence would add to each step; the disk
        # protocol rides the background writer. Contrast with the
        # synchronous save the pre-tiered path paid at every cadence hit.
        import tempfile

        from thunder_tpu.resilience.preemption import CheckpointManager
        from thunder_tpu.resilience.snapshot import SnapshotStore

        import shutil

        ck_dir = tempfile.mkdtemp(prefix="ttpu_bench_ck_")
        try:
            store = SnapshotStore(host=0, ring=2)
            SnapshotStore.pair(store, SnapshotStore(host=1, ring=2))
            cmgr = CheckpointManager(ck_dir, backoff_s=0, store=store,
                                     async_flush=True)
            stalls = []
            for i in range(6):
                t0 = time.perf_counter()
                cmgr.snapshot((p, o), i)
                stalls.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            cmgr.save((p, o), 99)
            sync_save_s = time.perf_counter() - t0
            cmgr.close()
        finally:
            shutil.rmtree(ck_dir, ignore_errors=True)
        stall_ms = med(stalls) * 1e3
        result["checkpoint_stall_ms_per_step"] = round(stall_ms, 3)
        result["checkpoint_sync_save_ms"] = round(sync_save_s * 1e3, 2)
        _log(f"checkpoint tiers: snapshot stall {stall_ms:.2f}ms "
             f"(replicated to buddy) vs {sync_save_s * 1e3:.0f}ms "
             f"synchronous save")

    # Aggregate MFU: the traced program computes the GLOBAL batch, so its
    # FLOPs divide across every chip — MFU is flops / (t · n · per-chip peak).
    spec = resolve_device_spec(args.device_spec)
    cost = trace_cost(extrace, spec)
    mfu = cost.total_flops / (iter_s * n * spec.peak_flops["bf16"]) if iter_s else 0.0

    _log(f"fsdp_tp mesh={factors} B={B} T={args.seq} compile {compile_s:.1f}s "
         f"iter {iter_s * 1e3:.1f}ms (synced {synced_s * 1e3:.1f}ms, strict "
         f"{strict_s * 1e3:.1f}ms) loss {loss0:.3f}->{loss_last:.3f} "
         f"MFU {mfu * 100:.2f}% [{spec.name} x{n}]")

    result.update({
        "metric": "multichip_fsdp_tp_train_iter",
        "value": round(iter_s, 4),
        "unit": "s",
        "n_devices": n,
        "mesh": factors,
        "model": args.model,
        "batch": B,
        "seq": args.seq,
        "train_iter_s": round(iter_s, 4),
        "train_iter_synced_s": round(synced_s, 4),
        "train_iter_strict_sync_s": round(strict_s, 4),
        "train_tokens_per_sec": round(B * args.seq / iter_s) if iter_s else 0,
        "train_mfu": round(mfu, 5),
        "device_spec": spec.name,
        "train_flops_per_step": cost.total_flops,
        "multichip_trace_claim_s": round(trace_s, 2),
        "multichip_xla_compile_s": round(compile_s, 2),
        "compile_phases": {
            "trace_claim_s": round(trace_s, 2),
            "static_analysis_s": round(static_s, 3),
            "predicted_peak_bytes": predicted_peak,
            "xla_backend_compile_s": round(
                jax_c1["backend_compile_s"] - jax_c0["backend_compile_s"], 2),
            "persistent_cache_get_s": round(
                jax_c1["cache_get_s"] - jax_c0["cache_get_s"], 2),
            "persistent_cache_hits": jax_c1["hits"] - jax_c0["hits"],
            "persistent_cache_misses": jax_c1["misses"] - jax_c0["misses"],
        },
    })

    # Static HLO audit of the compiled step executable (ISSUE 16): the
    # SPMD-partitioner-inserted collectives recovered from the compiled-HLO
    # text, classified per family, priced at the ring factors, and
    # schedule-analyzed — no profiler needed. The committed
    # spmd_collective_exposed_pct_static is the STATIC base the measured
    # spmd_collective_exposed_pct lane number is judged against, and the
    # baseline ROADMAP item 3's scheduling-hints work must move.
    t0 = time.perf_counter()
    try:
        from thunder_tpu.analysis.hlo_audit import audit_jitted

        hrep = audit_jitted(step, p, o, idx, tgt, device=spec)
        hrep.audit_s = time.perf_counter() - t0
        result["spmd_collective_exposed_pct_static"] = round(hrep.exposed_pct, 2)
        result["hlo_inserted_collectives"] = hrep.inserted_collectives
        result["hlo_static_collectives"] = {
            fam: {
                "count": agg["count"],
                "wire_bytes": int(agg["wire_bytes"]),
                "inserted": agg["inserted"],
            }
            for fam, agg in sorted(hrep.by_family.items())
        }
        result["compile_phases"]["hlo_audit_s"] = round(hrep.audit_s, 3)
        # Per-tier split of the audited wire (ISSUE 20): collectives whose
        # group fits inside one model-parallel block are charged to the
        # ICI tier, wider ones to DCN — the fleet timeline's static input
        # for its exposed-ICI/exposed-DCN critical-path classes
        # (observability/timeline.split_static_wire).
        from thunder_tpu.observability.timeline import split_static_wire

        tier = split_static_wire(hrep.sites, factors["tp"])
        result["hlo_wire_ici_us_static"] = round(tier["ici_us"], 2)
        result["hlo_wire_dcn_us_static"] = round(tier["dcn_us"], 2)
        result["hlo_wire_ici_frac_static"] = round(tier["ici_frac"], 4)
        _log(f"hlo audit: {hrep.n_ops} ops, {len(hrep.sites)} collectives "
             f"({hrep.inserted_collectives} partitioner-inserted), static "
             f"exposed {result['spmd_collective_exposed_pct_static']}% in "
             f"{hrep.audit_s:.2f}s: "
             + ", ".join(f"{f}={a['count']}" for f, a in sorted(hrep.by_family.items())))
    except Exception as e:  # noqa: BLE001 — the auditor is advisory here too
        _log(f"hlo audit failed (advisory): {type(e).__name__}: {e}")

    # Profiled run → per-collective measured wire time + overlap split.
    if not args.no_profile:
        import tempfile

        trace_dir = tempfile.mkdtemp(prefix="thunder_mc_prof_")
        try:
            scope_map = scope_map_of(step, p, o, idx, tgt)
        except Exception:
            scope_map = {}
        res = ttpu.profile(lambda: step(p, o, idx, tgt), trace_dir=trace_dir,
                           steps=args.profile_steps, warmup=1)
        if res["profiler"]:
            from thunder_tpu.observability.attribution import attribute

            attr = attribute(trace_dir, extra_scope_map=scope_map or None)
            steps = args.profile_steps
            coll = {
                cls: {
                    "us_per_step": round(row.us / steps, 1),
                    "hidden_us_per_step": round(row.hidden_us / steps, 1),
                    "exposed_us_per_step": round(row.exposed_us / steps, 1),
                    "calls": row.count,
                }
                for cls, row in sorted(attr.collective_summary().items())
            }
            busy = attr.device_busy_us / steps
            exposed = attr.exposed_collective_us / steps
            result["collectives"] = coll
            result["device_busy_us_per_step"] = round(busy, 1)
            result["collective_us_per_step"] = round(attr.collective_us / steps, 1)
            # Raw lane measurement of the SPMD (partitioner-inserted)
            # collectives. The committed headline collective_exposed_pct
            # moved to the explicit-collective workload at r03, where the
            # trace-level scheduler can actually prove hiding — this keeps
            # the r01/r02 measurement series alive under its own name.
            result["spmd_collective_exposed_pct"] = round(
                exposed / busy * 100.0, 2) if busy else 0.0
            _log(f"collectives: {attr.collective_us / steps:.0f}us/step on the wire "
                 f"({result['spmd_collective_exposed_pct']}% of device time exposed): "
                 + ", ".join(f"{c}={v['us_per_step']}us" for c, v in coll.items()))
        else:
            _log("profiler unavailable: collective attribution skipped")


# =============================================================================
# Workload 2: explicit-collective FSDP step (predicted vs measured overlap)
# =============================================================================


def bench_overlap(args, result: dict) -> None:
    """Explicit-collective FSDP×TP step through the comm scheduler (ISSUE 13).

    A K-layer fw+bw step whose collectives are trace-level ``dist_prims``
    under ``shard_map`` on the fsdp×tp mesh: per layer an fsdp
    ``synchronize`` gathers the sharded weight and a tp ``all_reduce``
    combines the partial activations; the grad transform emits the
    ``reduce_scatter``s. The run:

    1. stages + profiles the UNSCHEDULED trace (lane-segmentation table);
    2. fits an effective per-class ICI bandwidth from that measured table
       (``analysis.cost.calibrate_ici`` — the emulated mesh measures
       ~1000× the datasheet wire time, all rendezvous) so the scheduler's
       placement decisions are priced in the right order of magnitude;
    3. runs ``transforms/comm_schedule.schedule_collectives`` with the
       calibrated spec, restages, and profiles the SCHEDULED trace;
    4. joins the scheduler's static per-site hidden/exposed prediction
       (datasheet pricing — what real chips' latency-hiding scheduler
       realizes) against the measured lane table, per site.

    The committed headline ``collective_exposed_pct`` is the static
    prediction over the scheduled trace (exposed wire / total wire at the
    bench device spec); ``collective_exposed_pct_measured_lanes`` keeps the
    raw lane measurement, which is structurally ~100% exposed on the
    emulated CPU mesh (serial lanes — see docs/performance.md)."""
    import tempfile

    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    import thunder_tpu as ttpu
    import thunder_tpu.clang as clang
    from thunder_tpu.analysis import schedule as sched_mod
    from thunder_tpu.analysis.cost import (
        calibrate_ici,
        collective_sym_class,
        resolve_device_spec,
        trace_cost,
    )
    from thunder_tpu.core.pytree import tree_flatten
    from thunder_tpu.distributed import prims as dist
    from thunder_tpu.distributed.runtime import (
        compile_with_collectives,
        stage_collective_trace,
    )
    from thunder_tpu.observability.attribution import attribute, parse_scope
    from thunder_tpu.parallel import make_mesh
    from thunder_tpu.transforms.comm_schedule import schedule_collectives

    n = args.devices
    factors = mesh_factors(n)
    fsdp_g, tp_g = factors["fsdp"], factors["tp"]
    mesh = make_mesh(**factors)
    rng = np.random.RandomState(0)
    layers, d, B = 4, 256, 64
    ws = [rng.randn(d, d).astype(np.float32) * (1.0 / np.sqrt(d))
          for _ in range(layers)]
    x = rng.randn(B, d).astype(np.float32)

    def loss_traced(*flat_in):
        *w_shards, xv = flat_in
        h = xv
        for w_shard in w_shards:
            w_full = dist.synchronize(w_shard, "fsdp", fsdp_g, "fsdp")
            h = clang.matmul(h, clang.transpose(w_full, 0, 1))
            if tp_g > 1:
                # avg: the identity on replicated activations, but the real
                # tp wire pattern (and its grad all_reduce) in the trace.
                h = dist.all_reduce(h, "tp", tp_g, op="avg")
            h = clang.tanh(h)
        return clang.mean(clang.mul(h, h))

    # Trace on per-device shard shapes; call with the global arrays —
    # shard_map's in_specs do the splitting (tests/_dist_worker.py idiom).
    shards = tuple(w[: d // fsdp_g] for w in ws)
    w_spec = P("fsdp", None)
    in_specs = tuple([w_spec] * layers + [P()])
    out_specs = (P(), tuple([w_spec] * layers + [P()]))
    jf0, extrace = compile_with_collectives(
        loss_traced, shards + (x,), mesh, in_specs, out_specs, grad=True,
    )
    flat = [jnp.asarray(a) for a in (*ws, x)]
    tree_flatten(jf0(*flat))[0][0].block_until_ready()

    def _profile(jf, tag):
        trace_dir = tempfile.mkdtemp(prefix=f"thunder_mc_overlap_{tag}_")
        res = ttpu.profile(lambda: jf(*flat), trace_dir=trace_dir,
                           steps=args.profile_steps, warmup=1)
        if not res["profiler"]:
            return None
        hlo_text = None
        try:
            # The watchdog wrapper around the jitted fn delegates lower.
            if hasattr(jf, "lower"):
                hlo_text = jf.lower(*flat).compile().as_text()
        except Exception:
            hlo_text = None
        return attribute(trace_dir, hlo_text=hlo_text)

    spec = resolve_device_spec(args.device_spec)
    steps = max(1, args.profile_steps)

    def _measured_by_line(attr):
        """{trace line: (measured us/step, lane-hidden us/step)} for the
        scoped collective rows of one profile."""
        out = {}
        if attr is None:
            return out
        for key, row in attr.collectives.items():
            ref = parse_scope(key)
            if ref is not None:
                got = out.setdefault(ref.line, [0.0, 0.0])
                got[0] += row.us / steps
                got[1] += row.hidden_us / steps
        return out

    # -- 1+2: unscheduled profile → per-class ICI calibration -----------------
    attr0 = _profile(jf0, "unsched")
    cost0 = trace_cost(extrace, spec)
    meas0 = _measured_by_line(attr0)
    samples = []
    for r in cost0.rows:
        if r.kind != "collective" or not r.comm_bytes:
            continue
        m = meas0.get(r.index)
        if m and m[0] > 0:
            samples.append((collective_sym_class(r.sym), r.comm_bytes, m[0] / 1e6))
    calibrated = calibrate_ici(spec, samples)
    if calibrated.ici_class_bw:
        result["ici_calibration"] = {
            "source": ("fitted from this run's measured per-collective table "
                       "(unscheduled profile, lane segmentation)"),
            "datasheet_ici_bw": spec.ici_bw,
            "effective_bw_by_class": {
                k: round(v, 1) for k, v in calibrated.ici_class_bw.items()
            },
        }
        _log("ici calibration: " + ", ".join(
            f"{k}={v / 1e6:.2f}MB/s (datasheet {spec.ici_bw / 1e9:.0f}GB/s)"
            for k, v in calibrated.ici_class_bw.items()))

    # -- 3: schedule with calibrated wire prices, restage, re-profile ---------
    scheduled, srep = schedule_collectives(extrace, device=calibrated)
    if srep is not None:
        for line in srep.format().splitlines():
            _log(line)
        result["comm_schedule"] = {
            k: v for k, v in srep.to_tag().items() if k != "sites"
        }
    jf1 = stage_collective_trace(scheduled, mesh, in_specs, out_specs)
    tree_flatten(jf1(*flat))[0][0].block_until_ready()
    attr1 = _profile(jf1, "sched")
    meas1 = _measured_by_line(attr1)

    # -- 4: static per-site prediction joined against measured lanes ----------
    pred_before = sched_mod.predict_overlap(extrace, device=spec)
    pred_after = sched_mod.predict_overlap(scheduled, device=spec)
    cost1 = trace_cost(scheduled, calibrated)
    cal_wire = {r.index: r.roofline_s * 1e6 for r in cost1.rows
                if r.kind == "collective"}
    moves = {}
    if srep is not None:
        moves = {s.key: s for s in srep.sites}

    rows = []
    for so in sorted(pred_after.sites, key=lambda s: -s.wire_us):
        m = meas1.get(so.index, (None, None))
        mv = moves.get(so.key)
        rows.append({
            "collective": so.label(),
            "class": collective_sym_class(so.sym) or so.sym,
            "axis": so.axis,
            "moved_from": mv.index_before if mv and mv.moved else None,
            "predicted_wire_us": round(so.wire_us, 2),
            "predicted_wire_us_calibrated": round(cal_wire.get(so.index, 0.0), 1),
            "predicted_hidden_us": round(so.hidden_us, 2),
            "predicted_exposed_us": round(so.exposed_us, 2),
            "window_us": round(so.window_us, 2),
            "measured_us_per_step": round(m[0], 1) if m[0] is not None else None,
            "measured_hidden_lane_us_per_step": (
                round(m[1], 1) if m[1] is not None else None
            ),
        })

    # No silent caps: the committed table is top-k by predicted wire, with
    # the drop recorded and logged (ISSUE 13 satellite).
    k = max(1, args.overlap_top_k)
    result["overlap"] = rows[:k]
    result["overlap_sites_total"] = len(rows)
    result["overlap_sites_shown"] = min(k, len(rows))
    result["overlap_sites_dropped"] = max(0, len(rows) - k)
    if result["overlap_sites_dropped"]:
        _log(f"overlap table: showing {k} of {len(rows)} collective sites "
             f"({result['overlap_sites_dropped']} dropped; --overlap-top-k raises)")

    # Headline: the scheduled trace's static exposed fraction of total wire
    # at the bench device spec — the compile-time twin real chips realize
    # via the latency-hiding scheduler. The raw lane measurement stays
    # alongside (serial CPU lanes cannot overlap, so it reads ~100%).
    result["collective_exposed_pct"] = round(pred_after.exposed_pct, 2)
    result["collective_exposed_pct_unscheduled"] = round(pred_before.exposed_pct, 2)
    result["collective_exposed_basis"] = (
        "static schedule prediction (exposed wire / total wire at "
        f"device_spec={spec.name}) over the comm-scheduled trace; per-site "
        "join vs measured lanes in 'overlap'"
    )
    if attr1 is not None and attr1.device_busy_us:
        result["collective_exposed_pct_measured_lanes"] = round(
            attr1.exposed_collective_us / attr1.device_busy_us * 100.0, 2
        )
    # Renamed from r02's overlap_predicted_comm_s: the workload changed at
    # r03 (2-layer fsdp MLP -> 4-layer fsdp4·tp2 step), so the old key's
    # wire volume is not comparable and must not gate.
    result["overlap_predicted_wire_s"] = round(cost0.comm_s, 6)
    _log(f"overlap: static exposed {pred_before.exposed_pct:.1f}% -> "
         f"{pred_after.exposed_pct:.1f}% of wire after scheduling "
         f"({srep.moves if srep else 0} moves)")


# =============================================================================
# Driver
# =============================================================================


def run(args) -> dict:
    result: dict = {}
    bench_fsdp_tp(args, result)
    try:
        bench_overlap(args, result)
    except Exception as e:
        # The overlap workload is diagnostic; its failure must not lose the
        # timing series. The error is recorded so the smoke can assert on it.
        _log(f"overlap workload failed ({type(e).__name__}: {e})")
        result["overlap_error"] = f"{type(e).__name__}: {e}"
    return result


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="bench_multichip.py",
        description="FSDP×TP multichip training-step benchmark (MULTICHIP_BENCH series)",
    )
    p.add_argument("--devices", type=int, default=8)
    p.add_argument("--model", default="llama-tiny")
    p.add_argument("--batch", type=int, default=0, help="global batch (0 = auto)")
    p.add_argument("--seq", type=int, default=32)
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--profile-steps", type=int, default=3)
    p.add_argument("--overlap-top-k", type=int, default=16,
                   help="rows committed in the per-site overlap table (the "
                        "total/dropped site counts are always recorded — no "
                        "silent caps)")
    p.add_argument("--no-profile", action="store_true")
    p.add_argument("--resilience-overhead", action="store_true",
                   help="also measure watchdog+SDC-guard steady-state step "
                        "overhead vs the strict protocol (ISSUE 9; target <2%%)")
    p.add_argument("--device-spec", default=None,
                   help="cost-model device spec (default: autodetect)")
    p.add_argument("--out", default=None, help="also write the JSON to this path")
    args = p.parse_args(argv)

    import jax

    from thunder_tpu.benchmarks import device_description

    if len(jax.devices()) < args.devices:
        # This process has called jax.devices() and so holds whatever chip
        # there is; a child could not have it. No re-execution onto a CPU
        # mesh: its numbers would come out under device metric names.
        print(f"bench_multichip: --devices {args.devices} asked, jax reports "
              f"{device_description()}. For a functional run on a virtual CPU mesh set "
              f"JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count="
              f"{args.devices} outside, as the tests do.", file=sys.stderr)
        return 1

    # Annotated codegen so collective trace lines carry scopes in profiles.
    os.environ.setdefault("THUNDER_TPU_ANNOTATE_TRACES", "1")
    from thunder_tpu.api import _ensure_runtime

    _ensure_runtime()
    result = run(args)
    result["device"] = device_description()
    line = json.dumps(result)
    print(line)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
