#!/usr/bin/env python
"""Run the static trace verifier over the repo's example programs.

CI/tooling entry point for the analysis/ framework (see
docs/trace_invariants.md): every program below is traced, pushed through the
default pass pipeline (acquisition → DCE → CSE → claiming → del_last_used)
with `examine.lint`, and — for the gradient workloads — compiled end-to-end
under THUNDER_TPU_CHECKS=1 so each transform pass (autodiff joint rewrite,
autocast, RNG functionalization) is verified at the point it runs.

Exit status is non-zero if any ERROR-severity diagnostic is found.

The full run also executes the regression gate
(``scripts/perf_report.py --history --gate``) over the committed
``*_r*.json`` rounds, so a regression beyond threshold fails this script
loudly.

Usage:
    python scripts/lint_traces.py            # all programs + round gates
    python scripts/lint_traces.py gpt        # substring-filter by name
    python scripts/lint_traces.py --events LOG.jsonl [LOG2.jsonl ...]
        # replay observability event log(s) (THUNDER_TPU_EVENTS /
        # jit(events=...)): validates the JSONL schema and flags recompile
        # storms; several per-host logs are merged with stable ordering
        # (thunder_tpu.analysis.events; docs/observability.md)
    python scripts/lint_traces.py --static
        # static planner smoke (ISSUE 10; docs/trace_invariants.md): GPT
        # fwd and fwd+bwd predicted peak HBM within 15% of the
        # instrument="memory" measured high-water; fsdp4·tp2 collective
        # schedule certifies and uncertified reorders + donation/alias
        # hazards are flagged; the de-opt ladder under the chaos oom@<3
        # memory ceiling reaches its fitting level with strictly fewer
        # failed XLA compiles than blind climbing
    python scripts/lint_traces.py --chaos
        # resilience smoke (docs/robustness.md): run the GPT gradient
        # pipeline under a canned fault schedule (kernel raise, compile
        # failure, OOM, NaN poison) and fail on any unrecovered fault,
        # non-baseline-equal recovery, or missing degradation event in the
        # JSONL log (replayed through the correlation rule)
    python scripts/lint_traces.py --soak
        # fleet-autopilot soak smoke (ISSUE 11; docs/robustness.md "fleet
        # autopilot"): a short deterministic (seeded) scripts/soak_fleet.py
        # run on the 8-device virtual mesh — must end with zero unrecovered
        # faults and zero unactuated autopilot decisions, exercise at least
        # one decision of every policy class (elastic_resume,
        # quarantine_rerun, deopt_escalate, checkpoint_halt), and land a
        # per-fault recovery cost within the soak noise floor of the
        # committed SOAK_r*.json round; full runs gate the committed
        # series via perf_report --gate
    python scripts/lint_traces.py --ops
        # live ops-plane smoke (ISSUE 15; docs/observability.md "ops
        # plane"): start the per-host HTTP endpoint against a chaos'd GPT
        # step — /healthz must flip degraded on a seeded straggler stream,
        # /metrics must scrape mid-run with host labels AND the
        # always-export drop counter at 0, an injected hang must leave a
        # schema-valid flight-recorder dump, and the measured ops-plane
        # overhead must stay under 1% of the step time
    python scripts/lint_traces.py --hlo
        # HLO-auditor smoke (ISSUE 16; docs/trace_invariants.md "HLO
        # auditor"): the fsdp4·tp2 build_train_step executable's compiled
        # HLO must yield ≥1 partitioner-inserted collective of every
        # family the partitioner emits (all-gather, all-reduce, derived
        # reduce-scatter, collective-permute) with nonzero wire bytes, a
        # schema-valid report JSON, analyze cost <5% of the XLA compile,
        # and garbage HLO must degrade to a sharp_edge advisory without
        # breaking the compile
    python scripts/lint_traces.py --chaos-multihost
        # mesh-wide resilience smoke (ISSUE 9): the FSDP×TP training step
        # on a virtual 8-device mesh under a canned host-loss +
        # collective-hang + SDC schedule — collective hang must raise the
        # typed watchdog timeout naming trace line + suspected host,
        # host loss must checkpoint and elastically resume on the shrunk
        # fsdp2·tp2 mesh reproducing the uninterrupted loss trajectory,
        # SDC must be caught by the replica-checksum guard and re-run;
        # every fault_injected needs its paired recovery event
"""

from __future__ import annotations

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def _programs():
    """(name, fn, args) — the example-program corpus: the ops exercised by
    examples/train.py's training step plus representative small programs."""
    import thunder_tpu.torch as ttorch
    from thunder_tpu.models import gpt as m
    from thunder_tpu.core import dtypes

    rng = np.random.RandomState(0)
    x44 = rng.randn(4, 4).astype(np.float32)
    x48 = rng.randn(4, 8).astype(np.float32)
    w86 = rng.randn(6, 8).astype(np.float32)

    cfg = m.name_to_config("gpt-tiny")
    params = m.init_params(cfg, dtype=dtypes.float32, seed=0)
    idx = rng.randint(0, cfg.vocab_size, (2, 16)).astype(np.int32)
    tgt = np.roll(idx, -1, axis=1).astype(np.int32)

    return [
        ("elementwise-chain", lambda a: ((a * 2.0).tanh() + a).sum(), (x44,)),
        ("linear-gelu", lambda a, w: ttorch.sum(ttorch.gelu(ttorch.linear(a, w))), (x48, w86)),
        ("reduction-mix", lambda a: (a.sum(0) * a.mean()).sum(), (x44,)),
        ("dropout-rng", lambda a: ttorch.dropout(a, p=0.5, training=True).sum(), (x44,)),
        ("inplace-functionalized", _inplace_prog, (x44,)),
        ("gpt-tiny-forward", lambda p, i: m.forward(p, i, cfg), (params, idx)),
        ("gpt-tiny-loss", lambda p, i, t: m.loss_fn(p, i, t, cfg), (params, idx, tgt)),
    ]


def _inplace_prog(a):
    import thunder_tpu.torch as ttorch

    b = ttorch.abs(a)
    b += 1.0
    return ttorch.sum(b)


def _grad_workloads():
    """(name, staged callable, args) compiled with the verifier scoped on —
    exercises the grad/autocast/RNG transform passes the pipeline-level lint
    stages don't reach."""
    import thunder_tpu as ttpu
    from thunder_tpu.models import gpt as m
    from thunder_tpu.core import dtypes

    rng = np.random.RandomState(0)
    cfg = m.name_to_config("gpt-tiny")
    params = m.init_params(cfg, dtype=dtypes.float32, seed=0)
    idx = rng.randint(0, cfg.vocab_size, (2, 16)).astype(np.int32)
    tgt = np.roll(idx, -1, axis=1).astype(np.int32)
    loss = lambda p, i, t: m.loss_fn(p, i, t, cfg)  # noqa: E731

    return [
        ("gpt-tiny-backward", ttpu.value_and_grad(loss, executors=["jax"], debug_checks=True),
         (params, idx, tgt)),
        ("gpt-tiny-backward-autocast",
         ttpu.value_and_grad(loss, executors=["jax"], debug_checks=True, autocast="bfloat16"),
         (params, idx, tgt)),
    ]


def _replay(paths: list, storm_threshold: int) -> int:
    from thunder_tpu.analysis import Severity
    from thunder_tpu.analysis.events import format_replay, replay_events

    # One path keeps single-log semantics (per-line diagnostics); several are
    # merged with stable (ts, host, pid, seq) ordering before replay.
    source = paths[0] if len(paths) == 1 else paths
    summary, diags = replay_events(source, storm_threshold=storm_threshold)
    print(format_replay(summary, diags))
    n_errors = sum(1 for d in diags if d.severity >= Severity.ERROR)
    print(f"\nlint_traces --events: {n_errors} error(s), "
          f"{sum(1 for d in diags if d.severity == Severity.WARNING)} warning(s)")
    return 1 if n_errors else 0


def _bench_history_gate(glob_pat: str, min_rounds: int = 2) -> int:
    """Run the regression gate over one committed series of rounds
    (scripts/perf_report.py). Returns the number of errors (0 when fewer
    than ``min_rounds`` committed rounds exist; the SOAK_POD series passes
    ``min_rounds=1`` because its absolute federation invariants gate from
    the first committed round)."""
    import glob

    scripts_dir = os.path.dirname(os.path.abspath(__file__))
    repo_root = os.path.dirname(scripts_dir)
    paths = sorted(glob.glob(os.path.join(repo_root, glob_pat)))
    if len(paths) < min_rounds:
        return 0
    if scripts_dir not in sys.path:
        sys.path.insert(0, scripts_dir)
    from perf_report import run_history_gate

    print(f"--- bench regression gate (perf_report --history --gate) [{glob_pat}]")
    return run_history_gate(paths, gate=True)


def _hlo_smoke() -> int:
    """--hlo: re-exec this script on a virtual 8-device CPU mesh (the
    device-count flag must be set before jax initializes) and run
    :func:`_hlo_inner` there. Returns the error count."""
    import subprocess

    env = {
        "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
        "HOME": os.environ.get("HOME", "/root"),
        "PYTHONPATH": os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "THUNDER_TPU_RETRY_BACKOFF_S": "0",
    }
    cmd = [sys.executable, os.path.abspath(__file__), "--_hlo-inner"]
    print("--- hlo smoke (subprocess, 8 virtual devices)")
    r = subprocess.run(cmd, env=env, capture_output=True, text=True, timeout=1200)
    out = (r.stdout + r.stderr).strip().splitlines()
    for line in out[-40:]:
        print(f"    {line}")
    if r.returncode != 0:
        print(f"    FAILED: inner smoke exited {r.returncode}")
        return 1
    return 0


# Every key one committed HloScheduleReport.to_json() must carry for the
# static series (bench r05+, docs/performance.md "static HLO audit") to stay
# comparable.
_HLO_REPORT_REQUIRED_KEYS = (
    "v", "module", "device", "n_ops", "n_computations", "collectives",
    "inserted_collectives", "explicit_collectives", "fusions", "layout_copies",
    "host_transfers", "flops", "hbm_bytes", "comm_bytes", "compute_us",
    "wire_us", "hidden_us", "exposed_us", "exposed_pct", "sites",
)
_HLO_SITE_REQUIRED_KEYS = (
    "name", "opcode", "family", "computation", "group_size", "wire_bytes",
    "wire_us", "hidden_us", "exposed_us", "inserted", "derived",
)


def _hlo_inner() -> int:
    """The HLO-auditor smoke (ISSUE 16 acceptance), run with 8 virtual
    devices: the fsdp4·tp2 ``build_train_step`` executable's compiled HLO
    must yield ≥1 partitioner-inserted collective of every family the
    partitioner emits on this step (all-gather, all-reduce, reduce-scatter
    — CPU XLA spells it as all-reduce+shard-slice, recovered as derived —
    and collective-permute), each with nonzero wire bytes; the report's
    ``to_json()`` must be schema-valid; the analyze pass must cost <5% of
    the XLA compile it piggybacks on; garbage HLO must raise ``ValueError``
    from ``audit_hlo`` and, through the compile-phase path, degrade to a
    ``sharp_edge`` advisory with the compile unharmed."""
    import json
    import tempfile
    import time

    import numpy as np

    import thunder_tpu as ttpu
    from thunder_tpu.analysis import hlo_audit
    from thunder_tpu.core import dtypes
    from thunder_tpu.models import gpt as m
    from thunder_tpu.parallel import build_train_step, make_mesh
    from thunder_tpu.parallel.sharding import gpt_param_specs

    n_errors = 0
    cfg = m.name_to_config("gpt-tiny")
    params = m.init_params(cfg, dtype=dtypes.float32, seed=0)
    rng = np.random.RandomState(0)
    idx = rng.randint(0, cfg.vocab_size, (8, 16)).astype(np.int32)
    tgt = np.roll(idx, -1, axis=1).astype(np.int32)

    print("--- hlo smoke: audit the fsdp4-tp2 build_train_step executable")
    mesh = make_mesh(fsdp=4, tp=2)
    step, opt0 = build_train_step(
        cfg, params, idx, tgt, mesh=mesh, param_specs=gpt_param_specs(cfg, mesh),
        lr=1e-2, executors=["jax"], donate=False,
    )
    t0 = time.perf_counter()
    text = step.lower(params, opt0, idx, tgt).compile().as_text()
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    rep = hlo_audit.audit_hlo(text)
    analyze_s = time.perf_counter() - t0

    # Family coverage: the ISSUE 16 acceptance families, each inserted by
    # the partitioner (not explicit dist_prims) and carrying wire bytes.
    expected = ("all-gather", "all-reduce", "reduce-scatter", "collective-permute")
    bad = [f for f in expected
           if not ((agg := rep.by_family.get(f))
                   and agg["count"] >= 1 and agg["wire_bytes"] > 0
                   and agg["inserted"] >= 1)]
    if bad:
        n_errors += 1
        print(f"    FAILED: families missing/uninserted/zero-wire: {bad} "
              f"(got {sorted(rep.by_family)})")
    else:
        derived_rs = sum(1 for s in rep.sites if s.family == "reduce-scatter"
                         and s.derived)
        print("    families OK: " + ", ".join(
            f"{f}×{rep.by_family[f]['count']}" for f in expected)
            + f" ({rep.inserted_collectives} inserted, {derived_rs} derived "
            f"reduce-scatter), static exposed {rep.exposed_pct:.1f}%")

    js = rep.to_json()
    missing = [k for k in _HLO_REPORT_REQUIRED_KEYS if k not in js]
    site_missing = [k for k in _HLO_SITE_REQUIRED_KEYS
                    for s in js["sites"][:1] if k not in s]
    json.dumps(js)  # must be JSON-serializable end to end
    if missing or site_missing or not js["sites"]:
        n_errors += 1
        print(f"    FAILED: report schema (missing={missing}, "
              f"site_missing={site_missing}, sites={len(js['sites'])})")
    else:
        print(f"    schema OK: {len(_HLO_REPORT_REQUIRED_KEYS)} report keys, "
              f"{len(js['sites'])} sites serialized")

    if analyze_s >= 0.05 * compile_s:
        n_errors += 1
        print(f"    FAILED: analyze {analyze_s * 1e3:.0f}ms >= 5% of the "
              f"{compile_s:.2f}s XLA compile it piggybacks on")
    else:
        print(f"    overhead OK: analyze {analyze_s * 1e3:.0f}ms = "
              f"{analyze_s / compile_s * 100:.1f}% of the {compile_s:.2f}s "
              f"XLA compile (< 5%)")

    print("--- hlo smoke: garbage HLO degrades to a sharp_edge advisory")
    try:
        hlo_audit.audit_hlo("this is not an HLO module at all")
        n_errors += 1
        print("    FAILED: audit_hlo accepted garbage without a ValueError")
    except ValueError:
        pass

    # The compile-phase path: seed the same failure INSIDE the auditor the
    # api.py phase calls; the compile must succeed, the result must be
    # right, and the event log must carry the advisory sharp_edge.
    log = os.path.join(tempfile.mkdtemp(prefix="ttpu_hlo_"), "events.jsonl")
    real_parse = hlo_audit.parse_hlo_module
    hlo_audit.parse_hlo_module = lambda text: real_parse("seeded garbage")
    try:
        jf = ttpu.jit(lambda a: (a * 2.0).sum(), executors=["jax"], events=log)
        out = float(np.asarray(jf(np.ones((4, 4), np.float32))))
    except Exception as e:  # noqa: BLE001 — an escaped auditor error IS the failure
        n_errors += 1
        out = None
        print(f"    FAILED: corrupted auditor broke the compile: "
              f"{type(e).__name__}: {e}")
    finally:
        hlo_audit.parse_hlo_module = real_parse
    recs = [json.loads(l) for l in open(log)] if os.path.exists(log) else []
    advisory = [r for r in recs if r.get("kind") == "sharp_edge"
                and "hlo_audit failed (advisory)" in (r.get("message") or "")]
    if out is not None and out != 32.0:
        n_errors += 1
        print(f"    FAILED: compile under corrupted auditor returned {out}")
    elif out is not None and not advisory:
        n_errors += 1
        print(f"    FAILED: no advisory sharp_edge in the event log "
              f"(kinds={sorted({r.get('kind') for r in recs})})")
    elif out is not None:
        print("    advisory OK: compile unharmed (result exact), sharp_edge "
              f"recorded: {advisory[0]['message'][:72]}")

    print(f"\nlint_traces --hlo: {n_errors} error(s)")
    return n_errors


def _static_smoke() -> int:
    """--static: the static trace planner smoke (ISSUE 10). Three parts:

    1. **Liveness/OOM prediction**: the GPT-tiny forward and fwd+bwd
       pipelines compile with ``instrument="memory"``; the entry's
       statically predicted peak must sit within 15% of the measured
       high-water (``bytes_in_use`` where the backend reports it; on the
       CPU plugin, the planner's eager-allocation total vs the hook's
       cumulative estimate — same quantity, same tolerance).
    2. **Collective-schedule safety**: an fsdp4·tp2-shaped gradient trace
       certifies (both mesh axes present, grad's reduce_scatter included);
       an uncertified same-axis reorder MUST be flagged, a certified legal
       one MUST pass; seeded-bad donation/alias traces must each trip their
       sanitizer rule.
    3. **Planner-guided de-opt**: under the chaos ``oom@<3`` seam (a
       deterministic memory ceiling that keeps OOMing below ladder level 3)
       with ``THUNDER_TPU_HBM_BYTES`` between the padded and exact-shape
       predicted peaks, the ladder must jump L0→L3 in ONE recompile —
       strictly fewer failed XLA compiles than HEAD's blind climb (which
       pays one per level: 4 compiles to reach L3).
    """
    import json
    import tempfile

    os.environ.setdefault("THUNDER_TPU_RETRY_BACKOFF_S", "0")

    import numpy as np
    import thunder_tpu as ttpu
    import thunder_tpu.clang as clang
    import thunder_tpu.core.prims as tprims
    from thunder_tpu.analysis import Severity, certify, plan_liveness, verify
    from thunder_tpu.analysis import schedule as sched_mod
    from thunder_tpu.core import devices, dtypes
    from thunder_tpu.core.proxies import TensorProxy
    from thunder_tpu.core.trace import TraceCtx, from_trace, tracectx
    from thunder_tpu.distributed import prims as dist
    from thunder_tpu.models import gpt as m
    from thunder_tpu.observability.instrument import instrument_reports

    n_errors = 0
    rng = np.random.RandomState(0)
    cfg = m.name_to_config("gpt-tiny")
    params = m.init_params(cfg, dtype=dtypes.float32, seed=0)
    idx = rng.randint(0, cfg.vocab_size, (2, 16)).astype(np.int32)
    tgt = np.roll(idx, -1, axis=1).astype(np.int32)

    # -- 1. liveness prediction vs measured high-water ------------------------
    workloads = [
        ("gpt-fwd", ttpu.jit(lambda p, i: m.forward(p, i, cfg),
                             executors=["jax"], instrument="memory"),
         (params, idx)),
        ("gpt-fwd+bwd", ttpu.value_and_grad(
            lambda p, i, t: m.loss_fn(p, i, t, cfg),
            executors=["jax"], instrument="memory"),
         (params, idx, tgt)),
    ]
    for name, jf, wargs in workloads:
        jf(*wargs)
        entry = jf._lc_cs.cache_entries[0]
        predicted_peak = entry.stats.predicted_peak_bytes
        rep = next((r for r in instrument_reports(jf)
                    if r["hook"] == "MemoryHighWater"), None)
        if predicted_peak is None or rep is None:
            # The planner is advisory at compile time (degrades to None),
            # but the smoke's whole job is to gate it: count the failure
            # instead of crashing the gate script.
            n_errors += 1
            print(f"    FAILED: {name}: planner produced no prediction "
                  f"(predicted_peak={predicted_peak}, memory hook="
                  f"{'present' if rep else 'absent'})")
            continue
        plan = plan_liveness(entry.computation_traces[-1], include_rows=False)
        if rep["exact"]:
            predicted, measured, what = predicted_peak, rep["peak_bytes"], "peak"
        else:
            # CPU plugin: no bytes_in_use — the hook's estimate is the
            # cumulative produced-bytes total, compared against the plan's
            # eager-allocation total (same quantity, statically derived).
            predicted, measured, what = (
                plan.eager_alloc_bytes, rep["peak_bytes"], "eager-alloc",
            )
        err = abs(predicted - measured) / max(measured, 1)
        line = (f"{name}: predicted {what} {predicted / 1e6:.2f} MB vs measured "
                f"{measured / 1e6:.2f} MB ({err * 100:+.1f}%), "
                f"static peak {predicted_peak / 1e6:.2f} MB")
        if err > 0.15:
            n_errors += 1
            print(f"    FAILED (OOM-misprediction >15%): {line}")
        else:
            print(f"    {line}")

    # -- 2. schedule certificate + sanitizer seeded-bads ----------------------
    print("--- static smoke: fsdp4-tp2 schedule certificate")

    def _cpu_t(shape, name=None):
        return TensorProxy(name=name, shape=shape, dtype=dtypes.float32,
                           device=devices.Device("cpu"))

    from thunder_tpu.api import trace_program
    from thunder_tpu.core.proxies import DistParallelType
    from thunder_tpu.executors.passes import transform_for_execution
    from thunder_tpu.extend import resolve_executors
    from thunder_tpu.transforms.autodiff import grad_transform
    from thunder_tpu.transforms.common import dce

    w = rng.randn(4, 8).astype(np.float32)  # fsdp shard of a (16, 8) weight
    x = rng.randn(4, 8).astype(np.float32)

    def fsdp_tp_loss(w_shard, xv):
        w_full = dist.synchronize(w_shard, "fsdp", 4, "fsdp")
        h = clang.matmul(xv, clang.transpose(w_full, 0, 1))
        h = dist.all_reduce(h, "tp", 2)
        return clang.mean(clang.mul(h, h))

    _, comp = trace_program(fsdp_tp_loss, (w, x), {})
    comp = dce(comp)
    comp = grad_transform(comp, return_value=True)
    extrace = transform_for_execution(comp, resolve_executors(["jax"]))
    cert = sched_mod.stamp(extrace)
    axes = set(cert.axis_order)
    syms = [s.sym for s in cert.sites]
    if {"fsdp", "tp"} <= axes and "reduce_scatter" in syms:
        print(f"    certificate OK: {len(cert.sites)} sites on axes "
              f"{sorted(axes)}, grad reduce_scatter present, "
              f"{len(cert.movable_sites())} movable")
    else:
        n_errors += 1
        print(f"    FAILED: certificate incomplete (axes={axes}, syms={syms})")
    if any(d.severity >= Severity.ERROR for d in verify(extrace)):
        n_errors += 1
        print("    FAILED: planner rules fired on the clean fsdp-tp trace")

    # Uncertified reorder of two same-axis collectives must be flagged.
    coll_idx = [s.index for s in cert.sites if s.axis == "fsdp"]
    if len(coll_idx) >= 2:
        bad = from_trace(extrace)
        bs = list(extrace.bound_symbols)
        i, j = coll_idx[0], coll_idx[1]
        bs[i], bs[j] = bs[j], bs[i]
        bad.bound_symbols = bs
        diags = verify(bad, pass_name="uncertified reorder pass")
        if any(d.rule == "sched.uncertified-reorder" for d in diags):
            print("    uncertified same-axis reorder flagged OK")
        else:
            n_errors += 1
            print("    FAILED: uncertified collective reorder NOT flagged")
    else:
        n_errors += 1
        print("    FAILED: expected >=2 fsdp collectives to exercise reorder")

    # Seeded-bad donation/alias traces: each sanitizer rule must fire.
    def _seeded_bads():
        t1 = TraceCtx()
        with tracectx(t1):
            a = _cpu_t((4, 4))
            t1.args = (a,)
            out = clang.mul(a, a)
            tprims.python_return(out)
            t1.output = out
        t1.tags["donated_inputs"] = (a.name,)
        t1.tags["rerun_reads_inputs"] = True
        yield "donation.use-after-donation", t1

        t2 = TraceCtx()
        with tracectx(t2):
            a = _cpu_t((4, 4))
            t2.args = (a,)
            tprims.python_return(a)
            t2.output = a
        t2.tags["donated_inputs"] = (a.name,)
        yield "donation.donated-output", t2

        t3 = TraceCtx()
        with tracectx(t3):
            src = _cpu_t((4, 4))
            dst = _cpu_t((4, 4))
            t3.args = (src, dst)
            written = _cpu_t((4, 4))
        t3.bound_symbols.append(tprims.copy_.bind(src, dst, output=written))
        with tracectx(t3):
            tprims.python_return(dst)
        t3.output = dst
        yield "alias.entry-aliasing", t3

    for rule_id, trc in _seeded_bads():
        diags = verify(trc)
        if any(d.rule == rule_id and d.severity >= Severity.ERROR for d in diags):
            print(f"    {rule_id} fired on seeded-bad trace OK")
        else:
            n_errors += 1
            print(f"    FAILED: {rule_id} did not fire on its seeded-bad trace")

    # -- 3. planner-guided de-opt ladder under the chaos oom ceiling ----------
    print("--- static smoke: de-opt ladder jump under oom@<3")
    from thunder_tpu.analysis.liveness import predict_level_peaks

    xb = rng.randn(100, 64).astype(np.float32)  # batch 100 -> pow2 bucket 128
    wb = rng.randn(64, 64).astype(np.float32)

    def chain(xv, wv):
        h = clang.matmul(xv, wv)
        h = clang.tanh(h)
        h = clang.matmul(h, wv)
        return clang.sum(clang.mul(h, h))

    baseline = float(np.asarray(
        ttpu.jit(chain, executors=["jax"])(xb, wb)
    ))

    probe = ttpu.jit(chain, cache="symbolic values", symbolic_dims={0: (0,)},
                     executors=["jax"])
    probe(xb, wb)
    probe_entry = probe._lc_cs.cache_entries[0]
    peaks = predict_level_peaks(
        probe_entry.computation_traces[-1],
        sym_spec=probe_entry.sym_spec,
        true_extents=probe_entry.last_true_extents,
    )
    if not (peaks[3] and peaks[1] and peaks[3] < peaks[1]):
        n_errors += 1
        print(f"    FAILED: exact-shape peak should undercut padded ({peaks})")
        print(f"\nlint_traces --static: {n_errors} error(s)")
        return n_errors
    capacity = (peaks[1] + peaks[3]) // 2
    os.environ["THUNDER_TPU_HBM_BYTES"] = str(int(capacity))
    log = os.path.join(tempfile.mkdtemp(prefix="ttpu_static_"), "events.jsonl")
    try:
        jf = ttpu.jit(chain, cache="symbolic values", symbolic_dims={0: (0,)},
                      executors=["jax"], chaos="oom@<3*inf", events=log)
        out = float(np.asarray(jf(xb, wb)))
        cs = jf._lc_cs
        level = jf._lc_cd._deopt_level
        deopts = [json.loads(l) for l in open(log)
                  if json.loads(l).get("kind") == "compile_deopt"]
        blind_compiles = 1 + 3  # HEAD pays one failed compile per level to L3
        ok = (
            abs(out - baseline) < 1e-3 * max(abs(baseline), 1.0)
            and level == 3
            and cs.compile_count < blind_compiles
            and len(deopts) == 1
            and deopts[0].get("skipped_levels") == [1, 2]
            and deopts[0].get("predicted_peak_bytes")
        )
        if ok:
            print(f"    ladder jump OK: L0 -> L3 in {cs.compile_count} compiles "
                  f"(blind HEAD: {blind_compiles}), skipped {deopts[0]['skipped_levels']}, "
                  f"predicted {deopts[0]['predicted_peak_bytes'] / 1e3:.1f} KB vs "
                  f"capacity {capacity / 1e3:.1f} KB")
        else:
            n_errors += 1
            print(f"    FAILED: level={level} compiles={cs.compile_count} "
                  f"(blind={blind_compiles}) deopts={deopts} out={out} "
                  f"baseline={baseline}")
    finally:
        os.environ.pop("THUNDER_TPU_HBM_BYTES", None)

    print(f"\nlint_traces --static: {n_errors} error(s)")
    return n_errors


def _chaos_smoke() -> int:
    """--chaos: the resilience smoke (ISSUE 6 satellite). Runs the GPT
    gradient pipeline under a canned fault schedule — executor kernel raise,
    XLA compile failure, device OOM, NaN poisoning — asserting every fault
    recovers to the un-faulted baseline (bitwise) or raises the typed error
    naming its seam, and that the JSONL log carries the correlated
    ``fault_injected`` → degradation event pair for each injection (the
    replay's ``events.unrecovered-fault`` rule). Returns the error count."""
    import tempfile

    os.environ.setdefault("THUNDER_TPU_RETRY_BACKOFF_S", "0")

    import numpy as np
    import thunder_tpu as ttpu
    from thunder_tpu.analysis import Severity
    from thunder_tpu.analysis.events import format_replay, replay_events
    from thunder_tpu.core import dtypes
    from thunder_tpu.extend import OperatorExecutor, get_executor, register_executor
    from thunder_tpu.models import gpt as m
    from thunder_tpu.resilience import NonFiniteOutputError, chaos, demotion

    demotion.clear_quarantine()
    rng = np.random.RandomState(0)
    cfg = m.name_to_config("gpt-tiny")
    params = m.init_params(cfg, dtype=dtypes.float32, seed=0)
    idx = rng.randint(0, cfg.vocab_size, (2, 16)).astype(np.int32)
    tgt = np.roll(idx, -1, axis=1).astype(np.int32)
    loss = lambda p, i, t: m.loss_fn(p, i, t, cfg)  # noqa: E731

    log = os.path.join(tempfile.mkdtemp(prefix="ttpu_chaos_"), "events.jsonl")
    n_errors = 0

    def flat(out):
        from thunder_tpu.core.pytree import tree_flatten

        return [np.asarray(x) for x in tree_flatten(out)[0]]

    print("--- chaos smoke: un-faulted baseline")
    baseline = flat(ttpu.value_and_grad(loss, executors=["jax"])(params, idx, tgt))

    # A chaos-armed smoke executor claiming the erf prim (inside the GPT
    # MLP's gelu): the kernel-raise seam for an environment with no TPU
    # kernels (pallasex/flashex carry the same seam on real hardware). The
    # impl delegates to the jax executor's, so even an un-demoted claim is
    # bitwise-identical to the baseline.
    from thunder_tpu.core.prims import PrimIDs

    smoke_ex = get_executor("chaos_smoke")
    if smoke_ex is None:
        smoke_ex = OperatorExecutor("chaos_smoke")
        register_executor(smoke_ex)
        _jax_erf = get_executor("jax").get_impl(PrimIDs.ERF)

        def _smoke_erf(a, _jax_erf=_jax_erf):
            chaos.kernel_seam("chaos_smoke", "erf")
            return _jax_erf(a)

        smoke_ex.register_implementation(PrimIDs.ERF, fn=_smoke_erf)

    schedules = [
        ("kernel_raise (executor demotion)", ["chaos_smoke", "jax"],
         "kernel_raise@chaos_smoke*1", None),
        ("compile_fail + oom (de-opt ladder)", ["jax"], "compile_fail*1;oom*1", None),
        ("nan poison (isfinite guard)", ["jax"], "nan@matmul*1", "rerun-instrumented"),
    ]
    for name, executors, spec, on_nan in schedules:
        print(f"--- chaos smoke: {name} [{spec}]")
        jf = ttpu.value_and_grad(
            loss, executors=executors, events=log, chaos=spec, on_nan=on_nan
        )
        try:
            out = flat(jf(params, idx, tgt))
        except NonFiniteOutputError as e:
            if on_nan is None:
                n_errors += 1
                print(f"    FAILED: unexpected NonFiniteOutputError: {e}")
            else:
                print(f"    recovered loudly: {type(e).__name__} "
                      f"attributed to {e.symbol!r}")
            continue
        except Exception as e:  # an unrecovered fault escaped: that IS the failure
            n_errors += 1
            print(f"    FAILED (unrecovered fault): {type(e).__name__}: {e}")
            continue
        if on_nan is not None:
            n_errors += 1
            print("    FAILED: nan poison did not trip the isfinite guard")
        elif len(out) != len(baseline) or any(
            not np.array_equal(a, b) for a, b in zip(out, baseline)
        ):
            n_errors += 1
            print("    FAILED: recovered run is not bitwise-equal to baseline")
        else:
            print("    recovered, bitwise-equal to baseline")

    print("--- chaos smoke: event-log replay (correlation rule)")
    # Recompiles ARE the recovery mechanism under chaos (every demotion and
    # de-opt recompiles), so the storm heuristic gets headroom here; the
    # correlation rule is what this replay is for.
    summary, diags = replay_events(log, storm_threshold=16)
    print(format_replay(summary, diags))
    n_errors += sum(1 for d in diags if d.severity >= Severity.ERROR)
    if not summary.get("faults_injected"):
        n_errors += 1
        print("    FAILED: no fault_injected events recorded")
    demotion.clear_quarantine()
    print(f"\nlint_traces --chaos: {n_errors} error(s)")
    return n_errors


_SOAK_REQUIRED_KEYS = (
    "metric", "value", "unit", "seed", "n_devices", "mesh", "model", "steps",
    "soak_goodput_tokens_per_sec", "soak_tokens_per_sec",
    "soak_ideal_tokens_per_sec", "soak_goodput_ratio",
    "resilience_overhead_pct", "soak_wall_s", "soak_recovery_per_fault_s",
    "soak_faults_injected",
    "soak_fault_seams", "soak_overlapping_pairs", "soak_decisions",
    "soak_unrecovered", "soak_unactuated",
    # Tiered checkpointing (ISSUE 14).
    "checkpoint_stall_ms_per_step", "snapshot_every", "soak_snapshots",
    "soak_restore_tiers", "soak_restore_fallthroughs",
    # Live ops plane (ISSUE 15).
    "soak_ops_port", "soak_anomalies", "soak_anomalies_total",
    "soak_detection_lead", "soak_decisions_citing_anomaly",
    "soak_undetected_detector_classes", "soak_flightrec_dumps",
    "soak_flightrec_invalid", "soak_flightrec_missing",
)

# The hot loop's amortized checkpoint cost must stay snapshot-shaped (a
# device→host copy every few steps). A synchronous disk save leaking back
# onto the hot path costs ~100ms+ per cadence hit — far past this cap even
# on a loaded CI machine.
_SOAK_STALL_MS_PER_STEP_CAP = 25.0

# The four autopilot policy classes the smoke must see decided at least
# once (the schedule's REQUIRED_SEAMS guarantee the triggering faults).
_SOAK_POLICY_CLASSES = (
    "elastic_resume", "quarantine_rerun", "deopt_escalate", "checkpoint_halt",
)


def _torn_fallthrough_check() -> int:
    """Deterministic torn-write disk fall-through (ISSUE 14 satellite): a
    ``snap_torn`` background flush leaves its step directory WITHOUT the
    META commit marker; the tiered restore must skip the incomplete step
    and land on the older complete one — asserted from the replayed event
    log, not from in-process state. Returns the error count."""
    import json
    import tempfile

    import numpy as np

    import thunder_tpu.monitor as monitor
    from thunder_tpu.analysis.events import replay_events
    from thunder_tpu.resilience import chaos, elastic
    from thunder_tpu.resilience.preemption import CheckpointManager

    tmp = tempfile.mkdtemp(prefix="ttpu_torn_")
    log = os.path.join(tmp, "ev.jsonl")
    n_errors = 0
    monitor.set_event_log(log)
    try:
        mgr = CheckpointManager(os.path.join(tmp, "ck"), backoff_s=0,
                                async_flush=True)
        state = {"p": np.arange(8, dtype=np.float32)}
        mgr.save(state, 10)
        with chaos.chaos_scope("snap_torn"):
            mgr.snapshot(state, 20, flush=True)
            mgr.close()  # drain: the torn flush's events are in the log
        _, meta, tier, _tried = elastic.tiered_restore(mgr)
    finally:
        monitor.set_event_log(None)
    if not (tier == "disk" and meta["step"] == 10):
        n_errors += 1
        print(f"    FAILED: torn fall-through restored {tier}@{meta['step']} "
              f"(want disk@10)")
    summary, diags = replay_events(log)
    records = [json.loads(line) for line in open(log)]
    torn_flush = any(r["kind"] == "snapshot_flush" and not r["ok"]
                     and r.get("reason") == "torn" for r in records)
    skipped = any(r["kind"] == "checkpoint_restore" and not r["ok"]
                  for r in records)
    if not (torn_flush and skipped):
        n_errors += 1
        print(f"    FAILED: torn-write log shape (torn_flush={torn_flush}, "
              f"incomplete-skip={skipped})")
    if summary.get("unrecovered_faults"):
        n_errors += 1
        print(f"    FAILED: snap_torn unrecovered: "
              f"{summary['unrecovered_faults']}")
    if not n_errors:
        print("    torn-write fall-through OK: flush tore at step 20, "
              "restore skipped it and landed on disk@10")
    return n_errors


def _soak_smoke() -> int:
    """--soak: the fleet-autopilot soak smoke (ISSUE 11 satellite). Runs a
    short deterministic ``scripts/soak_fleet.py --smoke`` on the 8-device
    virtual mesh and asserts: zero unrecovered faults, zero unactuated
    decisions, at least one decision of EVERY policy class, every required
    seam kind injected, and a per-fault recovery cost within the soak
    noise floor of the committed ``SOAK_r*.json`` round. Tiered
    checkpointing (ISSUE 14): also asserts a bounded
    ``checkpoint_stall_ms_per_step``, at least one RAM-tier and one
    disk-tier restore, a restore that FELL THROUGH an invalid tier, and
    (in-process) the deterministic torn-write disk fall-through — all from
    replayed event logs. Full runs additionally gate the committed series
    with ``perf_report --gate``. Returns the error count."""
    import glob
    import json
    import subprocess
    import tempfile

    scripts_dir = os.path.dirname(os.path.abspath(__file__))
    repo_root = os.path.dirname(scripts_dir)
    out_path = os.path.join(tempfile.mkdtemp(prefix="ttpu_soak_smoke_"), "soak.json")
    cmd = [sys.executable, os.path.join(scripts_dir, "soak_fleet.py"),
           "--smoke", "--seed", "7", "--out", out_path]
    print("--- soak smoke: " + " ".join(cmd))
    n_errors = 0
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=1500)
    for line in r.stderr.strip().splitlines()[-20:]:
        print(f"    {line}")
    if r.returncode != 0:
        print(f"    FAILED: soak_fleet exited {r.returncode}")
        return 1
    with open(out_path) as f:
        result = json.load(f)

    missing = [k for k in _SOAK_REQUIRED_KEYS if k not in result]
    if missing:
        n_errors += 1
        print(f"    FAILED: soak JSON missing keys: {missing}")
    else:
        print(f"    schema OK ({len(_SOAK_REQUIRED_KEYS)} required keys)")

    if result.get("soak_unrecovered") or result.get("soak_unactuated"):
        n_errors += 1
        print(f"    FAILED: unrecovered={result.get('soak_unrecovered')} "
              f"unactuated={result.get('soak_unactuated')}")
    else:
        print("    correlation OK: zero unrecovered faults, zero unactuated "
              "decisions")

    decisions = result.get("soak_decisions") or {}
    absent = [c for c in _SOAK_POLICY_CLASSES if not decisions.get(c)]
    if absent:
        n_errors += 1
        print(f"    FAILED: policy classes never decided: {absent} "
              f"(got {decisions})")
    else:
        print("    policy coverage OK: " + ", ".join(
            f"{c}×{decisions[c]}" for c in _SOAK_POLICY_CLASSES))

    seams = result.get("soak_fault_seams") or {}
    if len(seams) < 5 or not result.get("soak_overlapping_pairs"):
        n_errors += 1
        print(f"    FAILED: schedule diversity (seams={sorted(seams)}, "
              f"overlaps={result.get('soak_overlapping_pairs')})")
    else:
        print(f"    schedule OK: {result.get('soak_faults_injected')} faults "
              f"across {len(seams)} seam kinds, "
              f"{result['soak_overlapping_pairs']} overlapping pair(s)")

    # Tiered checkpointing (ISSUE 14): the soak's own replay computed these
    # from its event log (soak_fleet derives them via replay_events).
    stall = result.get("checkpoint_stall_ms_per_step")
    if not isinstance(stall, (int, float)) or not (
            0.0 < stall <= _SOAK_STALL_MS_PER_STEP_CAP):
        n_errors += 1
        print(f"    FAILED: checkpoint_stall_ms_per_step={stall} not in "
              f"(0, {_SOAK_STALL_MS_PER_STEP_CAP}] — snapshots missing, or "
              f"disk IO leaked back onto the hot path")
    else:
        print(f"    stall OK: {stall:.2f} ms/step over "
              f"{result.get('soak_snapshots')} snapshots")
    tiers = result.get("soak_restore_tiers") or {}
    ram = (tiers.get("local") or 0) + (tiers.get("peer") or 0)
    if not ram or not tiers.get("disk"):
        n_errors += 1
        print(f"    FAILED: restore-tier coverage {tiers} (need >=1 RAM-tier "
              f"and >=1 disk-tier restore)")
    elif not result.get("soak_restore_fallthroughs"):
        n_errors += 1
        print(f"    FAILED: no restore fell through an invalid tier "
              f"(snap_corrupt must force the checksum gate; tiers={tiers})")
    else:
        print(f"    tiers OK: " + ", ".join(
            f"{t}×{n}" for t, n in sorted(tiers.items()))
            + f"; {result['soak_restore_fallthroughs']} fall-through(s)")
    # Live ops plane (ISSUE 15): the detectors must have flagged every
    # detector-covered fault class, an anomaly must PRECEDE the decision
    # citing it (positive detection lead), and every timeout/halt must have
    # left a schema-valid flight-recorder dump.
    anomalies = result.get("soak_anomalies") or {}
    if result.get("soak_undetected_detector_classes") or not anomalies:
        n_errors += 1
        print(f"    FAILED: detector coverage (anomalies={anomalies}, "
              f"missed={result.get('soak_detector_classes_missed')})")
    elif not (isinstance(result.get("soak_detection_lead"), (int, float))
              and result["soak_detection_lead"] > 0):
        n_errors += 1
        print(f"    FAILED: detection lead "
              f"{result.get('soak_detection_lead')} not > 0 (no decision "
              f"cited a preceding anomaly)")
    else:
        print("    detectors OK: " + ", ".join(
            f"{k}×{n}" for k, n in sorted(anomalies.items()))
            + f"; lead {result['soak_detection_lead']:.2f}s over "
            f"{result.get('soak_decisions_citing_anomaly')} cited decision(s)")
    if (result.get("soak_flightrec_invalid")
            or result.get("soak_flightrec_missing")
            or not result.get("soak_flightrec_dumps")):
        n_errors += 1
        print(f"    FAILED: flight recorder "
              f"(dumps={result.get('soak_flightrec_dumps')}, "
              f"invalid={result.get('soak_flightrec_invalid')}, "
              f"missing={result.get('soak_flightrec_missing')})")
    else:
        print(f"    flight recorder OK: "
              + ", ".join(f"{r}×{n}" for r, n in sorted(
                  (result.get('soak_flightrec_by_reason') or {}).items()))
              + " dump(s), all schema-valid")

    n_errors += _torn_fallthrough_check()

    # Goodput sanity vs the committed round. The goodput RATIO swings with
    # the machine's ideal step time (the CPU mesh cannot hold it steady
    # run to run), so the portable comparator is the recovery cost charged
    # per fault — wall time beyond ideal-speed useful steps, per injection
    # — bounded by the soak noise floor (perf_report._SOAK_NOISE_FLOORS),
    # doubled for the smoke's shorter run (one-off rebuild costs amortize
    # over fewer faults).
    committed = sorted(glob.glob(os.path.join(repo_root, "SOAK_r*.json")))
    goodput = result.get("soak_goodput_tokens_per_sec")
    per_fault = result.get("soak_recovery_per_fault_s")
    if not isinstance(goodput, (int, float)) or goodput <= 0:
        n_errors += 1
        print(f"    FAILED: no usable goodput ({goodput})")
    elif committed and isinstance(per_fault, (int, float)):
        if scripts_dir not in sys.path:
            sys.path.insert(0, scripts_dir)
        from perf_report import noise_floor

        with open(committed[-1]) as f:
            ref = json.load(f).get("soak_recovery_per_fault_s")
        floor = 2 * noise_floor("per_fault_s", "soak_goodput")
        if isinstance(ref, (int, float)) and abs(per_fault - ref) > floor:
            n_errors += 1
            print(f"    FAILED: recovery cost {per_fault:.2f}s/fault vs "
                  f"committed {ref:.2f} (floor ±{floor:.1f}s)")
        else:
            print(f"    goodput OK: {goodput:.0f} tok/s; recovery "
                  f"{per_fault:.2f}s/fault (committed {ref}, floor "
                  f"±{floor:.1f}s)")

    n_errors += _bench_history_gate("SOAK_r*.json")
    print(f"\nlint_traces --soak: {n_errors} error(s)")
    return n_errors


# The committed SOAK_POD schema (scripts/soak_pod.py — ISSUE 18): the
# federation invariants the smoke and the committed-round gate both read.
_POD_REQUIRED_KEYS = (
    "metric", "value", "unit", "n_devices", "n_slices", "mesh", "model",
    "steps", "soak_pod_goodput_tokens_per_sec", "soak_pod_wall_s",
    "soak_pod_degraded_steps", "soak_pod_degraded_tokens_per_sec",
    "soak_pod_full_width", "soak_pod_final_width", "soak_pod_min_width",
    "soak_pod_shrinks", "soak_pod_regrows", "soak_pod_restarts",
    "soak_pod_slice_loss_restores", "soak_pod_slice_loss_nonpeer_restores",
    "soak_pod_disk_restores_after_anchor", "soak_pod_restore_tiers",
    "soak_pod_decisions", "soak_pod_unrecovered", "soak_pod_unactuated",
    "soak_pod_replay_errors",
)


def _federation_smoke() -> int:
    """--federation: the slice-failure-domain smoke (ISSUE 18 satellite).
    Runs ``scripts/soak_pod.py --smoke`` — 2 emulated slices × 2 devices,
    one scripted whole-slice loss — and asserts the elastic cycle
    completed inside the CI budget: the fleet shrank (one shrink_dp,
    degraded steps at reduced width), trained through the loss, regrew to
    full DP width (one regrow_dp, final == full), the victim's state came
    back from the cross-slice buddy's PEER-RAM tier with disk untouched
    past the step-0 anchor, and the replayed ledger correlates clean (zero
    unrecovered / unactuated / replay errors, no process restart). Full
    runs additionally gate the committed ``SOAK_POD_r*.json`` round's
    absolute invariants via ``perf_report --gate``. Returns the error
    count."""
    import json
    import subprocess
    import tempfile
    import time

    scripts_dir = os.path.dirname(os.path.abspath(__file__))
    out_path = os.path.join(tempfile.mkdtemp(prefix="ttpu_fed_smoke_"),
                            "pod.json")
    cmd = [sys.executable, os.path.join(scripts_dir, "soak_pod.py"),
           "--smoke", "--seed", "7", "--out", out_path]
    print("--- federation smoke: " + " ".join(cmd))
    n_errors = 0
    t0 = time.perf_counter()
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=600)
    elapsed = time.perf_counter() - t0
    for line in r.stderr.strip().splitlines()[-12:]:
        print(f"    {line}")
    if r.returncode != 0:
        print(f"    FAILED: soak_pod exited {r.returncode}")
        return 1
    with open(out_path) as f:
        result = json.load(f)

    missing = [k for k in _POD_REQUIRED_KEYS if k not in result]
    if missing:
        n_errors += 1
        print(f"    FAILED: pod JSON missing keys: {missing}")
    else:
        print(f"    schema OK ({len(_POD_REQUIRED_KEYS)} required keys)")

    # The acceptance wall: shrink -> degraded training -> regrow, on CPU,
    # inside a minute (compiles for both widths included).
    if elapsed >= 60.0:
        n_errors += 1
        print(f"    FAILED: smoke took {elapsed:.1f}s (budget 60s)")
    else:
        print(f"    budget OK: shrink->train->regrow in {elapsed:.1f}s")

    full = result.get("soak_pod_full_width")
    if not (result.get("soak_pod_shrinks") == 1
            and result.get("soak_pod_regrows") == 1
            and result.get("soak_pod_degraded_steps", 0) > 0
            and result.get("soak_pod_min_width", full) < full
            and result.get("soak_pod_final_width") == full
            and not result.get("soak_pod_restarts")):
        n_errors += 1
        print(f"    FAILED: elastic cycle (shrinks="
              f"{result.get('soak_pod_shrinks')} regrows="
              f"{result.get('soak_pod_regrows')} degraded="
              f"{result.get('soak_pod_degraded_steps')} widths "
              f"{result.get('soak_pod_min_width')}->"
              f"{result.get('soak_pod_final_width')}/{full})")
    else:
        print(f"    elastic cycle OK: width {full}->"
              f"{result.get('soak_pod_min_width')}->{full}, "
              f"{result.get('soak_pod_degraded_steps')} degraded step(s)")

    if (not result.get("soak_pod_slice_loss_restores")
            or result.get("soak_pod_slice_loss_nonpeer_restores")
            or result.get("soak_pod_disk_restores_after_anchor")):
        n_errors += 1
        print(f"    FAILED: peer-tier proof (restores="
              f"{result.get('soak_pod_slice_loss_restores')} nonpeer="
              f"{result.get('soak_pod_slice_loss_nonpeer_restores')} "
              f"disk_after_anchor="
              f"{result.get('soak_pod_disk_restores_after_anchor')})")
    else:
        print(f"    peer-tier proof OK: tiers "
              f"{result.get('soak_pod_restore_tiers')}")

    if (result.get("soak_pod_unrecovered")
            or result.get("soak_pod_unactuated")
            or result.get("soak_pod_replay_errors")):
        n_errors += 1
        print(f"    FAILED: replay (unrecovered="
              f"{result.get('soak_pod_unrecovered')} unactuated="
              f"{result.get('soak_pod_unactuated')} errors="
              f"{result.get('soak_pod_replay_errors')})")
    else:
        print("    correlation OK: zero unrecovered faults, zero "
              "unactuated decisions")

    n_errors += _bench_history_gate("SOAK_POD_r*.json", min_rounds=1)
    print(f"\nlint_traces --federation: {n_errors} error(s)")
    return n_errors


def _ops_smoke() -> int:
    """--ops: live ops-plane smoke (ISSUE 15; docs/observability.md "ops
    plane"). Starts the per-host HTTP server against a chaos'd GPT step and
    asserts the four acceptance behaviors: /healthz flips degraded on a
    seeded straggler (streaming detectors), /metrics scrapes mid-run with
    host labels + the always-export drop counter, an injected hang leaves a
    schema-valid flight-recorder dump, and the measured ops-plane overhead
    stays under 1% of the step time (with exactly zero taps installed when
    the plane is off). Returns the error count."""
    import json
    import tempfile
    import time
    import urllib.error
    import urllib.request

    import thunder_tpu as ttpu
    import thunder_tpu.monitor as monitor
    from thunder_tpu.analysis import Severity
    from thunder_tpu.analysis.events import replay_events
    from thunder_tpu.core import dtypes
    from thunder_tpu.models import gpt as m
    from thunder_tpu.observability import events as obs_events
    from thunder_tpu.observability import opsplane
    from thunder_tpu.observability.detect import DetectorConfig
    from thunder_tpu.resilience import chaos, watchdog
    from thunder_tpu.resilience.preemption import CheckpointManager, run_training

    n_errors = 0
    tmp = tempfile.mkdtemp(prefix="ttpu_ops_")
    fr_dir = os.path.join(tmp, "flightrec")
    plane = monitor.serve(port=0, flightrec_dir=fr_dir,
                          detectors=DetectorConfig(min_samples=6, cooldown=20))
    print(f"--- ops smoke: server on 127.0.0.1:{plane.port}")

    def get(route):
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{plane.port}{route}", timeout=10) as r:
                return r.status, r.read().decode()
        except urllib.error.HTTPError as e:
            return e.code, e.read().decode()

    cfg = m.name_to_config("gpt-tiny")
    params = m.init_params(cfg, dtype=dtypes.float32, seed=0)
    idx = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (2, 32)).astype(np.int32)
    jf = ttpu.jit(lambda p, i: m.forward(p, i, cfg), executors=["jax"])

    def step_fn(state):
        out = jf(params, idx)
        return state, float(np.asarray(out).mean())

    step_fn(None)  # compile outside the measured/chaos'd loop
    t0 = time.perf_counter()
    for _ in range(5):
        step_fn(None)
    step_s = (time.perf_counter() - t0) / 5

    code, body = get("/healthz")
    before = json.loads(body)["status"]

    # A chaos'd training run: clean baseline steps, then a seeded straggler
    # (sub-timeout slowdown inside the guarded step) the detectors must
    # flag; /metrics is scraped MID-RUN from the step callback.
    ccfg = chaos.ChaosConfig(rules=[], seed=0)
    scraped = {}

    def on_loss(step, loss):
        if step == 11:
            ccfg.rules.append(chaos.FaultRule(
                "straggler", target="step", count=6,
                delay_s=max(0.25, step_s * 4)))
        if step == 18:
            scraped["code"], scraped["body"] = get("/metrics")

    with chaos.chaos_scope(ccfg):
        run_training(step_fn, None, 24,
                     manager=CheckpointManager(os.path.join(tmp, "ck")),
                     watchdog_timeout_s=60.0, on_loss=on_loss)

    code, body = get("/healthz")
    after = json.loads(body)
    anomalies = [a.kind for a in plane.bank.recent_anomalies()]
    if before != "ok" or after["status"] == "ok" or not anomalies:
        n_errors += 1
        print(f"    FAILED: healthz did not flip on the straggler "
              f"(before={before}, after={after['status']}, "
              f"anomalies={anomalies})")
    else:
        print(f"    healthz OK: ok -> {after['status']} on anomalies "
              f"{sorted(set(anomalies))}")

    mtext = scraped.get("body") or ""
    if (scraped.get("code") != 200
            or "thunder_tpu_event_log_dropped_total" not in mtext
            or 'host="' not in mtext):
        n_errors += 1
        print(f"    FAILED: mid-run /metrics scrape (code="
              f"{scraped.get('code')}, drop-counter present: "
              f"{'thunder_tpu_event_log_dropped_total' in mtext}, "
              f"host label present: {'host=' in mtext})")
    else:
        print(f"    /metrics OK mid-run: {len(mtext.splitlines())} lines, "
              f"host-labelled, always-export drop counter present")

    # An injected hang must turn into a typed timeout AND a schema-valid
    # flight-recorder dump carrying its preceding context.
    with chaos.chaos_scope("collective_hang~30"):
        try:
            watchdog.guard_call(lambda: None, (), fn_name="gpt_step",
                                timeout_s=0.2)
            n_errors += 1
            print("    FAILED: injected hang did not raise")
        except watchdog.CollectiveTimeoutError:
            pass
    import glob as _glob

    dumps = _glob.glob(os.path.join(fr_dir, "*collective_timeout.jsonl"))
    if not dumps:
        n_errors += 1
        print("    FAILED: no flight-recorder dump for the hang")
    else:
        summary, diags = replay_events(dumps[-1])
        errs = [d for d in diags if d.severity >= Severity.ERROR]
        kinds = summary.get("kinds", {})
        if errs or not kinds.get("collective_timeout") \
                or not summary.get("flightrec_dumps"):
            n_errors += 1
            print(f"    FAILED: dump replay ({len(errs)} error(s), "
                  f"kinds={kinds})")
        else:
            print(f"    flight recorder OK: {os.path.basename(dumps[-1])} "
                  f"({summary['lines']} records, schema-valid, "
                  f"0 correlation errors)")
    code, body = get("/debug/flightrec")
    if code != 200 or not json.loads(body).get("path"):
        n_errors += 1
        print(f"    FAILED: /debug/flightrec ({code}: {body[:120]})")
    code, body = get("/debug/state")
    state = json.loads(body) if code == 200 else {}
    if code != 200 or "cache" not in state or "autopilot" not in state:
        n_errors += 1
        print(f"    FAILED: /debug/state ({code})")

    # Overhead: the ops plane's per-step cost is one tap per emitted event
    # (steady state: one step_time event per step). Composed against the
    # measured step time — an A/B wall-clock diff at <1% would drown in
    # host noise.
    N = 20_000
    t0 = time.perf_counter()
    for _ in range(N):
        obs_events.emit_event("step_time", fn="overhead_probe", step=0, s=0.01)
    tap_ns = (time.perf_counter() - t0) / N * 1e9
    ops_pct = tap_ns / (step_s * 1e9) * 100.0
    monitor.shutdown_ops()
    if obs_events.ops_active():
        n_errors += 1
        print("    FAILED: taps still installed after shutdown_ops()")
    if ops_pct >= 1.0:
        n_errors += 1
        print(f"    FAILED: ops-plane overhead {ops_pct:.3f}% of the "
              f"{step_s * 1e3:.1f}ms step (budget < 1%)")
    else:
        print(f"    overhead OK: {tap_ns:.0f}ns/event = {ops_pct:.4f}% of "
              f"the {step_s * 1e3:.1f}ms step (< 1%); plane off installs "
              f"zero taps")

    print(f"\nlint_traces --ops: {n_errors} error(s)")
    return n_errors


def _roofline_smoke() -> int:
    """--roofline: continuous roofline ledger smoke (ISSUE 19;
    docs/performance.md "continuous roofline ledger"). On the CPU backend,
    asserts the tentpole acceptance behaviors end to end: a duty-cycled
    sampler on a gpt-tiny forward produces a schema-valid per-op ledger
    (>= 10 rows, every row in roofline.ROW_FIELDS) served live at
    /debug/roofline; a seeded mispriced op (its static roofline bound
    deflated 8x under the detectors' feet) trips a typed cost_model_drift
    anomaly through the DetectorBank; the armed-but-not-due per-step cost
    stays under 1% of the step; and with sampling off, zero probes run.
    Ends with the committed ROOFLINE_r*.json series gate. Returns the
    error count."""
    import json
    import time
    import urllib.error
    import urllib.request

    # Before any jit: annotated codegen is what stamps L<idx>.<sym> scopes
    # into HLO metadata so profiler rows attribute back to trace lines.
    os.environ.setdefault("THUNDER_TPU_ANNOTATE_TRACES", "1")

    import thunder_tpu as ttpu
    import thunder_tpu.monitor as monitor
    from thunder_tpu.models import gpt as m
    from thunder_tpu.observability import roofline as roofline_mod
    from thunder_tpu.observability.detect import DetectorConfig
    from thunder_tpu.observability.roofline import ROW_FIELDS, RooflineSampler

    n_errors = 0
    plane = monitor.serve(port=0,
                          detectors=DetectorConfig(min_samples=6, cooldown=20))
    print(f"--- roofline smoke: ops server on 127.0.0.1:{plane.port}")

    def get(route):
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{plane.port}{route}", timeout=10) as r:
                return r.status, r.read().decode()
        except urllib.error.HTTPError as e:
            return e.code, e.read().decode()

    cfg = m.name_to_config("gpt-tiny")
    params = m.init_params(cfg)
    idx = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (2, 32)).astype(np.int32)
    jf = ttpu.jit(lambda p, i: m.forward(p, i, cfg), executors=["jax"])
    jf(params, idx)  # compile outside the sampled loop
    t0 = time.perf_counter()
    for _ in range(5):
        np.asarray(jf(params, idx))
    step_s = (time.perf_counter() - t0) / 5

    # OFF (the default: THUNDER_TPU_ROOFLINE_EVERY unset -> every=0):
    # maybe_sample must never probe.
    off = RooflineSampler(jf)
    for _ in range(8):
        off.maybe_sample(jf, params, idx)
    if off.every != 0 or off.probes != 0 or len(off.ledger) != 0:
        n_errors += 1
        print(f"    FAILED: sampler off still probed (every={off.every}, "
              f"probes={off.probes})")
    else:
        print("    off OK: every=0 by default, 8 steps, zero probes")

    # ON: every=4 over 12 steps = exactly 3 probes; the ledger must come
    # back schema-valid with enough per-op rows to be a baseline.
    sampler = monitor.roofline(jf, every=4)
    for _ in range(12):
        sampler.maybe_sample(jf, params, idx)
    snap = sampler.ledger.snapshot()
    bad_rows = [r for r in snap["rows"] if set(r) != set(ROW_FIELDS)]
    priced = [r for r in snap["rows"] if r["roofline_us"] is not None]
    if (sampler.probes != 3 or snap["ops"] < 10 or bad_rows
            or len(priced) < 10):
        n_errors += 1
        print(f"    FAILED: ledger (probes={sampler.probes}, "
              f"ops={snap['ops']}, schema violations={len(bad_rows)}, "
              f"priced rows={len(priced)})")
    else:
        print(f"    ledger OK: 12 steps -> 3 probes, {snap['ops']} op rows, "
              f"schema-valid, {len(priced)} with roofline ceilings")

    code, body = get("/debug/roofline")
    live = json.loads(body) if code == 200 else {}
    if code != 200 or not live.get("enabled") \
            or live.get("ledger", {}).get("ops") != snap["ops"]:
        n_errors += 1
        print(f"    FAILED: /debug/roofline ({code}: {body[:120]})")
    else:
        print(f"    /debug/roofline OK: live ledger, "
              f"{live['ledger']['ops']} ops, {live['probes']} probes")

    # Seeded mispriced op: deflate the hottest op's static bound 8x in the
    # sampler's cost rows — the next probes' measured/predicted ratio walks
    # out of the band and the DetectorBank must raise cost_model_drift.
    top = sampler.ledger.rows()[0]
    seeded = 0
    for r in sampler._cost.rows:
        if r.sym == top.sym and r.index == top.line:
            r.roofline_s /= 8.0
            seeded += 1
    tripped = None
    for i in range(10):
        sampler.sample(jf, params, idx)
        kinds = [a.kind for a in plane.bank.recent_anomalies()]
        if "cost_model_drift" in kinds:
            tripped = i + 1
            break
    if not seeded or tripped is None:
        n_errors += 1
        print(f"    FAILED: seeded mispriced op ({top.label}, {seeded} cost "
              f"row(s) deflated) raised no cost_model_drift "
              f"(anomalies={sorted(set(kinds))})")
    else:
        a = next(a for a in plane.bank.recent_anomalies()
                 if a.kind == "cost_model_drift")
        print(f"    drift OK: {top.label} deflated 8x -> cost_model_drift "
              f"({a.severity}, ratio {a.value / a.baseline:.1f}x baseline) "
              f"after {tripped} probe(s)")

    # Overhead: the armed-but-not-due per-step cost is tick()'s counter
    # bump + modulo (maybe_sample then dispatches fn unchanged). Composed
    # against the measured step.
    N = 50_000
    armed = RooflineSampler(jf, every=10**9)
    t0 = time.perf_counter()
    for _ in range(N):
        armed.tick()
    tick_ns = (time.perf_counter() - t0) / N * 1e9
    tick_pct = tick_ns / (step_s * 1e9) * 100.0
    if tick_pct >= 1.0:
        n_errors += 1
        print(f"    FAILED: armed duty-cycle overhead {tick_pct:.3f}% of "
              f"the {step_s * 1e3:.1f}ms step (budget < 1%)")
    else:
        print(f"    overhead OK: {tick_ns:.0f}ns/step armed = "
              f"{tick_pct:.4f}% of the {step_s * 1e3:.1f}ms step (< 1%)")

    monitor.shutdown_roofline()
    monitor.shutdown_ops()

    # The committed per-op series must gate (single round: absolute
    # invariants — >= 10 schema-valid rows with per-op gate keys).
    n_errors += _bench_history_gate("ROOFLINE_r*.json", min_rounds=1)

    print(f"\nlint_traces --roofline: {n_errors} error(s)")
    return n_errors


def _critpath_smoke() -> int:
    """--critpath: fleet critical-path ledger smoke (ISSUE 20;
    docs/observability.md "fleet timeline"). Drives a synthetic 4-host
    fleet through the armed TimelineRecorder and asserts the tentpole
    acceptance behaviors end to end: injected per-host clock skews are
    recovered from the lockstep-barrier rendezvous records within
    tolerance; per-step breakdowns assemble a schema-valid ledger served
    live at /debug/critpath (and a ``timeline`` component in /healthz); a
    seeded straggler host trips a ``bottleneck_shift`` anomaly through the
    DetectorBank naming that host; the static/predicted-vs-measured
    exposed-collective cross-check agrees within the noise floor; and the
    armed per-step cost stays under 1% of a measured gpt-tiny step. Ends
    with the committed CRITPATH_r*.json series gate. Returns the error
    count."""
    import json
    import time
    import urllib.error
    import urllib.request

    import thunder_tpu as ttpu
    import thunder_tpu.monitor as monitor
    from thunder_tpu.models import gpt as m
    from thunder_tpu.observability.detect import DetectorConfig
    from thunder_tpu.observability.timeline import CLASSES

    n_errors = 0
    plane = monitor.serve(
        port=0,
        detectors=DetectorConfig(
            min_samples=6, cooldown=20,
            critpath_min_steps=4, critpath_straggler_frac=0.25,
            critpath_cooldown=0,
        ),
    )
    print(f"--- critpath smoke: ops server on 127.0.0.1:{plane.port}")

    def get(route):
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{plane.port}{route}", timeout=10) as r:
                return r.status, r.read().decode()
        except urllib.error.HTTPError as e:
            return e.code, e.read().decode()

    # A real measured step for the overhead budget denominator.
    cfg = m.name_to_config("gpt-tiny")
    params = m.init_params(cfg)
    idx = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (2, 32)).astype(np.int32)
    jf = ttpu.jit(lambda p, i: m.forward(p, i, cfg), executors=["jax"])
    jf(params, idx)
    t0 = time.perf_counter()
    for _ in range(5):
        np.asarray(jf(params, idx))
    step_s = (time.perf_counter() - t0) / 5

    # Armed recorder over a synthetic 4-host fleet: injected skews the
    # estimator must RECOVER (the falsifiable alignment loop), a static
    # wire split charging exposed-ICI/DCN, and the schedule certificate's
    # predicted exposed-pct for the three-way cross-check. event_sample=8
    # is the at-scale config: emitted events and gauge refreshes ride a
    # 1-in-8 duty cycle while the estimator/ledger/detector feed keep
    # full per-step fidelity (the assertions below all read in-process
    # state, so sampling cannot mask a recovery failure).
    injected = {"h0": 0.0, "h1": 0.12, "h2": -0.08, "h3": 0.04}
    rec = monitor.critpath(bank=plane.bank, emulated_skew_s=injected,
                           event_sample=8)
    rec.set_static_wire(0.10, 0.05, static_exposed_pct=15.0)
    rec.predicted_exposed_pct = 15.0

    BASE, DELAY, STALL = 0.050, 0.030, 0.004
    hosts = sorted(injected)
    for step in range(16):
        spans = {}
        for h in hosts:
            sp = dict(rec.static_spans(BASE))
            d = DELAY if (h == "h3" and 6 <= step < 14) else 0.0
            stall = STALL if step % 2 == 0 else 0.0
            sp["total_s"] = BASE + d + stall
            sp["stall_s"] = stall
            spans[h] = sp
            rec.note_collective(h, step, fn="fleet_step", s=0.0, step=step)
        rec.record_step(step, spans)

    # Skew recovery: estimates are relative to the fleet-median clock, so
    # compare against the injected offsets re-centered the same way.
    ests = rec.skew_estimates()
    med = sorted(injected.values())
    med = (med[1] + med[2]) / 2.0
    centered = {h: v - med for h, v in injected.items()}
    err_ms = max(abs(e.offset_s - centered[h]) * 1e3
                 for h, e in ests.items()) if ests else float("inf")
    outliers = [h for h, e in ests.items() if e.outlier]
    if len(ests) != 4 or err_ms > 5.0 or outliers:
        n_errors += 1
        print(f"    FAILED: skew recovery (hosts={len(ests)}, "
              f"err={err_ms:.3f}ms, outliers={outliers})")
    else:
        print(f"    skew OK: 4 hosts recovered within {err_ms:.3f}ms of "
              f"injected (120/-80/40ms spread), no false outliers")

    # Schema-valid ledger: every breakdown row carries the typed classes,
    # fractions sum to 1, and the straggler steps name the seeded host.
    snap = rec.ledger.snapshot(last=16)
    rows = snap["last_steps"]
    bad = [r for r in rows
           if set(r) != {"step", "total_s", "classes", "slowest_host",
                         "n_hosts"}
           or not set(r["classes"]) <= set(CLASSES)]
    fsum = sum(snap["fractions"].values())
    strag = snap["straggler_hosts"]
    if (snap["steps"] != 16 or bad or abs(fsum - 1.0) > 0.02
            or strag.get("h3", 0) < 6):
        n_errors += 1
        print(f"    FAILED: ledger (steps={snap['steps']}, "
              f"schema violations={len(bad)}, frac_sum={fsum:.3f}, "
              f"straggler_hosts={strag})")
    else:
        print(f"    ledger OK: 16 steps, schema-valid rows, fractions sum "
              f"{fsum:.3f}, straggler-wait on h3 x{strag['h3']}")

    # The seeded straggler must trip bottleneck_shift NAMING the host.
    shifts = [a for a in plane.bank.recent_anomalies()
              if a.kind == "bottleneck_shift"]
    named = [a for a in shifts if a.suspect_host == "h3"]
    if not named:
        n_errors += 1
        print(f"    FAILED: seeded straggler h3 raised no host-named "
              f"bottleneck_shift (got {[(a.kind, a.suspect_host) for a in shifts]})")
    else:
        a = named[0]
        print(f"    detector OK: bottleneck_shift ({a.severity}, "
              f"{a.detector}) names h3, straggler frac {a.value:.2f} vs "
              f"band {a.baseline:.2f}")

    # Static/predicted-vs-measured exposed-collective cross-check: the
    # synthetic spans are static-priced, so the deltas must sit inside the
    # perf gate's 10-point noise floor.
    cc = rec.crosscheck()
    d_static = cc.get("delta_static_pct")
    d_pred = cc.get("delta_predicted_pct")
    if (d_static is None or abs(d_static) > 10.0
            or d_pred is None or abs(d_pred) > 10.0):
        n_errors += 1
        print(f"    FAILED: exposed-pct cross-check ({cc})")
    else:
        print(f"    crosscheck OK: measured {cc['measured_exposed_pct']:.1f}% "
              f"vs static {cc['static_exposed_pct']:.1f}% "
              f"(d {d_static:+.2f}) / scheduler {cc['predicted_exposed_pct']:.1f}% "
              f"(d {d_pred:+.2f})")

    # Live surfaces: /debug/critpath serves the ledger + skew + crosscheck;
    # /healthz carries the timeline component (>= 2 hosts, aligned).
    code, body = get("/debug/critpath")
    live = json.loads(body) if code == 200 else {}
    if (code != 200 or not live.get("enabled")
            or live.get("ledger", {}).get("steps") != 16
            or "skew" not in live or "crosscheck" not in live):
        n_errors += 1
        print(f"    FAILED: /debug/critpath ({code}: {body[:120]})")
    else:
        print(f"    /debug/critpath OK: live ledger, "
              f"{live['ledger']['steps']} steps, "
              f"{len(live['skew'])} skew estimates")
    code, body = get("/healthz")
    verdict = json.loads(body) if body else {}
    tl_comp = (verdict.get("components") or {}).get("timeline")
    if tl_comp is None or tl_comp.get("hosts") != 4:
        n_errors += 1
        print(f"    FAILED: /healthz timeline component missing or wrong "
              f"({tl_comp})")
    else:
        print(f"    /healthz OK: timeline component "
              f"{tl_comp.get('status')}, {tl_comp['hosts']} hosts, "
              f"min confidence {tl_comp.get('min_confidence')}")

    # Overhead: the armed fleet-step cost (4 barrier records + one fold +
    # duty-cycled events/gauges) against the measured step, same protocol
    # as the roofline smoke. This one process plays ALL four hosts — a
    # real deployment spreads the barrier records across processes and
    # only the driver folds — so the budget holds the per-host share
    # under 1% while the full emulated composition is printed alongside.
    # Off-path (recorder not armed) is a None check in the driver —
    # literally zero.
    N = 2_000
    spans = {h: dict(rec.static_spans(BASE), total_s=BASE) for h in hosts}
    t0 = time.perf_counter()
    for i in range(N):
        for h in hosts:
            rec.note_collective(h, 1000 + i, fn="fleet_step", s=0.0,
                                step=1000 + i)
        rec.record_step(1000 + i, spans)
    per_step_ns = (time.perf_counter() - t0) / N * 1e9
    per_host_ns = per_step_ns / len(hosts)
    pct = per_host_ns / (step_s * 1e9) * 100.0
    if pct >= 1.0:
        n_errors += 1
        print(f"    FAILED: armed per-host cost {per_host_ns:.0f}ns = "
              f"{pct:.3f}% of the {step_s * 1e3:.1f}ms step (budget < 1%; "
              f"full {len(hosts)}-host emulation {per_step_ns:.0f}ns)")
    else:
        print(f"    overhead OK: {per_host_ns:.0f}ns/step/host armed = "
              f"{pct:.4f}% of the {step_s * 1e3:.1f}ms step (< 1%; full "
              f"{len(hosts)}-host emulation {per_step_ns:.0f}ns)")

    monitor.shutdown_critpath()
    monitor.shutdown_ops()

    # The committed fleet round must gate (single round: absolute
    # invariants — class coverage, skew recovery, attribution, citation).
    n_errors += _bench_history_gate("CRITPATH_r*.json", min_rounds=1)

    print(f"\nlint_traces --critpath: {n_errors} error(s)")
    return n_errors


def _chaos_multihost_smoke() -> int:
    """--chaos-multihost: re-exec this script on a virtual 8-device CPU mesh
    (the device-count flag must be set before jax initializes) and run
    :func:`_chaos_multihost_inner` there. Returns the error count."""
    import subprocess

    env = {
        "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
        "HOME": os.environ.get("HOME", "/root"),
        "PYTHONPATH": os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "THUNDER_TPU_RETRY_BACKOFF_S": "0",
    }
    cmd = [sys.executable, os.path.abspath(__file__), "--_chaos-multihost-inner"]
    print("--- chaos-multihost smoke (subprocess, 8 virtual devices)")
    r = subprocess.run(cmd, env=env, capture_output=True, text=True, timeout=1200)
    out = (r.stdout + r.stderr).strip().splitlines()
    for line in out[-40:]:
        print(f"    {line}")
    if r.returncode != 0:
        print(f"    FAILED: inner smoke exited {r.returncode}")
        return 1
    return 0


def _chaos_multihost_inner() -> int:
    """The mesh-wide chaos matrix (ISSUE 9 acceptance), run with 8 virtual
    devices: collective-hang → typed watchdog timeout naming trace line +
    suspected host; host-loss-at-step → checkpoint agreement → elastic
    resume on the shrunk mesh reproducing the uninterrupted loss
    trajectory; SDC injection → replica-checksum divergence → quarantine +
    re-run; all with paired fault_injected/recovery events validated by the
    replay correlation rule."""
    import json
    import tempfile

    import numpy as np

    import thunder_tpu.monitor as monitor
    from thunder_tpu.analysis import Severity
    from thunder_tpu.analysis.events import format_replay, replay_events
    from thunder_tpu.core import dtypes
    from thunder_tpu.models import gpt as m
    from thunder_tpu.parallel import build_train_step, make_mesh
    from thunder_tpu.parallel.sharding import gpt_param_specs
    from thunder_tpu.parallel.train import opt_state_specs
    from thunder_tpu.resilience import chaos, elastic, watchdog
    from thunder_tpu.resilience.preemption import CheckpointManager, HostLost, run_training

    tmp = tempfile.mkdtemp(prefix="ttpu_mc_chaos_")
    log = os.path.join(tmp, "events.jsonl")
    monitor.set_event_log(log)
    n_errors = 0
    N_STEPS = 5
    LOSS_STEP = 2

    cfg = m.name_to_config("gpt-tiny")
    params = m.init_params(cfg, dtype=dtypes.float32, seed=0)
    rng = np.random.RandomState(0)
    idx = rng.randint(0, cfg.vocab_size, (8, 16)).astype(np.int32)
    tgt = np.roll(idx, -1, axis=1).astype(np.int32)

    def build(mesh):
        specs = gpt_param_specs(cfg, mesh)
        step, opt0 = build_train_step(
            cfg, params, idx, tgt, mesh=mesh, param_specs=specs, lr=1e-2,
            executors=["jax"], donate=False,
        )

        def step_fn(state):
            p, o = state
            p, o, loss = step(p, o, idx, tgt)
            return (p, o), float(np.asarray(loss))

        return step_fn, opt0, specs

    mesh8 = make_mesh(fsdp=4, tp=2)
    step8, opt0, specs8 = build(mesh8)
    state0 = (params, opt0)

    print("--- chaos-multihost: un-faulted baseline trajectory")
    _, baseline = run_training(
        step8, state0, N_STEPS, manager=CheckpointManager(os.path.join(tmp, "base"))
    )
    print(f"    losses: {['%.4f' % x for x in baseline]}")

    print("--- chaos-multihost: collective hang -> typed watchdog timeout")
    # Join against PR 8's straggler data: host_health over synthetic per-host
    # step logs flags host 3; the timeout error must name it.
    hl = []
    for host in range(4):
        p = os.path.join(tmp, f"host{host}.jsonl")
        with open(p, "w") as f:
            for s in range(4):
                t = 0.4 if host == 3 else 0.1
                f.write(json.dumps({"v": 1, "ts": float(s), "seq": s, "pid": 1,
                                    "host": host, "kind": "step_time",
                                    "fn": "step", "step": s, "s": t}) + "\n")
        hl.append(p)
    summary, _ = monitor.host_health(hl)
    from thunder_tpu.distributed.runtime import compile_with_collectives
    from jax.sharding import PartitionSpec as P

    meshf = make_mesh(fsdp=8)
    w = rng.randn(16, 8).astype(np.float32) * 0.1
    x = rng.randn(4, 8).astype(np.float32)

    def loss_traced(w_shard, x):
        from thunder_tpu.distributed import prims as dist
        import thunder_tpu.clang as clang

        w_full = dist.synchronize(w_shard, "fsdp", 8, "fsdp")
        h = clang.matmul(x, clang.transpose(w_full, 0, 1))
        return clang.mean(clang.mul(h, h))

    jf, extrace = compile_with_collectives(
        loss_traced, (w[:2], x), meshf, (P("fsdp", None), P()),
        (P(), (P("fsdp", None), P())), grad=True,
    )
    watchdog.configure(0.25)
    try:
        with chaos.chaos_scope("collective_hang~5.0"):
            jf(w, x)
        n_errors += 1
        print("    FAILED: hang did not time out")
    except watchdog.CollectiveTimeoutError as e:
        ok_line = any("synchronize" in ln for ln in e.trace_lines)
        ok_host = e.suspected_host == summary["stragglers"][0]
        if ok_line and ok_host:
            print(f"    typed timeout OK: lines={e.trace_lines[:2]} "
                  f"suspect=host{e.suspected_host}")
        else:
            n_errors += 1
            print(f"    FAILED: lines={e.trace_lines} suspect={e.suspected_host}")
    finally:
        watchdog.configure(None)

    print("--- chaos-multihost: host loss -> checkpoint -> elastic resume (fsdp2-tp2)")
    mgr = CheckpointManager(os.path.join(tmp, "elastic"))
    try:
        with chaos.chaos_scope(f"host_loss@{LOSS_STEP}"):
            run_training(step8, state0, N_STEPS, manager=mgr, mesh=mesh8)
        n_errors += 1
        print("    FAILED: host loss did not fire")
    except HostLost as e:
        mesh4 = make_mesh(fsdp=2, tp=2)
        step4, _, specs4 = build(mesh4)
        st, start = elastic.elastic_resume(
            mgr, state0, mesh=mesh4, specs=(specs4, opt_state_specs(specs4))
        )
        if start != LOSS_STEP:
            n_errors += 1
            print(f"    FAILED: resumed at {start}, expected {LOSS_STEP}")
        cont = []
        state = st
        for _ in range(start, N_STEPS):
            state, loss = step4(state)
            cont.append(loss)
        if np.allclose(cont, baseline[LOSS_STEP:], rtol=1e-5):
            print(f"    elastic resume OK: {['%.4f' % x for x in cont]} matches "
                  f"the uninterrupted trajectory (reduction-order tolerance)")
        else:
            n_errors += 1
            print(f"    FAILED: resumed trajectory {cont} != baseline "
                  f"{baseline[LOSS_STEP:]}")

    print("--- chaos-multihost: SDC injection -> checksum guard -> re-run")
    try:
        with chaos.chaos_scope("sdc*1"):
            _, sdc_losses = run_training(
                step8, state0, N_STEPS,
                manager=CheckpointManager(os.path.join(tmp, "sdc")),
                sdc_guard=True,
            )
        if sdc_losses == baseline:
            print("    SDC quarantine + re-run OK: trajectory bitwise-equal")
        else:
            n_errors += 1
            print(f"    FAILED: SDC trajectory {sdc_losses} != {baseline}")
    except Exception as e:
        n_errors += 1
        print(f"    FAILED: {type(e).__name__}: {e}")

    print("--- chaos-multihost: event-log replay (correlation rule)")
    summary, diags = replay_events(log, storm_threshold=16)
    print(format_replay(summary, diags))
    n_errors += sum(1 for d in diags if d.severity >= Severity.ERROR)
    need = ("fault_injected", "collective_timeout", "host_loss",
            "checkpoint_save", "elastic_resume", "sdc_suspect", "sdc_rerun")
    missing = [k for k in need if not summary["kinds"].get(k)]
    if missing:
        n_errors += 1
        print(f"    FAILED: missing event kinds: {missing}")
    if summary.get("unrecovered_faults"):
        n_errors += 1
        print(f"    FAILED: unrecovered faults: {summary['unrecovered_faults']}")
    monitor.set_event_log(None)
    print(f"\nlint_traces --chaos-multihost: {n_errors} error(s)")
    return n_errors


_USAGE = ("usage: lint_traces.py [pattern] | --static | --chaos | "
          "--chaos-multihost | --soak | --federation | --hlo | "
          "--roofline | --critpath | --events <log.jsonl> [...] "
          "[--storm-threshold N]")


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)

    if "--_chaos-multihost-inner" in argv:
        return 1 if _chaos_multihost_inner() else 0

    if "--_hlo-inner" in argv:
        return 1 if _hlo_inner() else 0

    if "--hlo" in argv:
        return 1 if _hlo_smoke() else 0

    if "--chaos-multihost" in argv:
        return 1 if _chaos_multihost_smoke() else 0

    if "--static" in argv:
        print("--- static smoke: liveness prediction vs instrument='memory'")
        return 1 if _static_smoke() else 0


    if "--soak" in argv:
        return 1 if _soak_smoke() else 0

    if "--federation" in argv:
        return 1 if _federation_smoke() else 0

    if "--ops" in argv:
        return 1 if _ops_smoke() else 0

    if "--roofline" in argv:
        return 1 if _roofline_smoke() else 0

    if "--critpath" in argv:
        return 1 if _critpath_smoke() else 0

    if "--chaos" in argv:
        return 1 if _chaos_smoke() else 0

    if "--events" in argv:
        i = argv.index("--events")
        paths = []
        for a in argv[i + 1:]:
            if a.startswith("--"):
                break
            paths.append(a)
        storm = 4
        if "--storm-threshold" in argv:
            j = argv.index("--storm-threshold")
            try:
                storm = int(argv[j + 1])
            except (IndexError, ValueError):
                print(_USAGE, file=sys.stderr)
                return 2
        if not paths:
            print(_USAGE, file=sys.stderr)
            return 2
        try:
            return _replay(paths, storm)
        except OSError as e:
            print(f"lint_traces --events: cannot read {paths}: {e}", file=sys.stderr)
            return 2

    pattern = argv[0] if argv else ""

    from thunder_tpu.analysis import Severity, TraceVerificationError
    from thunder_tpu.examine import lint

    n_errors = n_warnings = 0

    for name, fn, args in _programs():
        if pattern not in name:
            continue
        print(f"--- lint: {name}")
        # Kernel executors are environment-sensitive; the jax executor claims
        # every prim, which is what the pipeline verification needs.
        diags = lint(fn, *args, executors=["jax"], verbose=False)
        errs = [d for d in diags if d.severity >= Severity.ERROR]
        warns = [d for d in diags if d.severity == Severity.WARNING]
        n_errors += len(errs)
        n_warnings += len(warns)
        for d in errs + warns:
            print(d.format())
        print(f"    {len(errs)} error(s), {len(warns)} warning(s)")

    for name, staged, args in _grad_workloads():
        if pattern not in name:
            continue
        print(f"--- verify (compiled, debug_checks=True): {name}")
        try:
            staged(*args)
            print("    all passes verified clean")
        except TraceVerificationError as e:
            n_errors += 1
            print(f"    FAILED: {e}")

    # CI half of the perf observatory (ISSUE 5/8): a committed round
    # regressing beyond threshold fails the lint run, not just a human's eye.
    if not pattern:
        n_errors += _bench_history_gate("SOAK_r*.json")
        n_errors += _bench_history_gate("SOAK_POD_r*.json", min_rounds=1)
        n_errors += _bench_history_gate("ROOFLINE_r*.json", min_rounds=1)
        n_errors += _bench_history_gate("CRITPATH_r*.json", min_rounds=1)

    print(f"\nlint_traces: {n_errors} error(s), {n_warnings} warning(s)")
    return 1 if n_errors else 0


if __name__ == "__main__":
    raise SystemExit(main())
